"""GenQSGD trainer: the driver that strings rounds together.

Uses the distributed round from :mod:`repro.fed.runtime` (works on 1 CPU
device or a full mesh alike) with a step-size sequence from
:mod:`repro.core.step_rules` and the offline-optimized (K, B, Γ) from
:mod:`repro.opt` when requested.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Callable, Iterator, Optional

import jax
import jax.numpy as jnp
import numpy as np

from ..configs.base import ArchConfig
from ..core.step_rules import StepRule
from ..fed import sharding as SH
from ..fed.runtime import FedConfig, make_round_fn
from ..obs import REGISTRY as _METRICS
from ..obs import trace as _trace
from ..obs.metrics import GLOBAL_SWITCH as _OBS_ON
from . import checkpoint as CKPT


def round_comm_bits(fed: FedConfig, dim: int, cohort=None) -> float:
    """Wire bits one round moves: worker uploads + the server multicast,
    priced by the same codec table the cost-layer optimizer uses.

    ``cohort`` (an index array, only under client sampling) restricts the
    upload sum to the workers that actually participated this round."""
    codecs = fed.codecs()
    idx = range(fed.n_workers) if cohort is None else cohort
    up = sum(codecs[int(i)].wire_bits(dim) for i in idx)
    return up + fed.server_codec().wire_bits(dim)

__all__ = ["TrainState", "GenQSGDTrainer", "round_comm_bits"]


@dataclasses.dataclass
class TrainState:
    params: object
    round: int
    history: list


class GenQSGDTrainer:
    def __init__(self, api, cfg: ArchConfig, fed: FedConfig, mesh,
                 step_rule: StepRule, checkpoint_dir: Optional[str] = None):
        self.api = api
        self.cfg = cfg
        self.fed = fed
        self.mesh = mesh
        self.rule = step_rule
        self.ckpt_dir = checkpoint_dir
        round_fn = make_round_fn(api, cfg, fed, mesh)
        self._round = jax.jit(round_fn)

    def init(self, key, dtype=jnp.float32) -> TrainState:
        params = self.api.init_params(key, self.cfg, dtype=dtype)
        # placed as the round returns them, so round 1 reuses round 0's
        # executable instead of compiling again for a new input placement
        params = jax.device_put(params,
                                SH.param_shardings(params, self.mesh))
        return TrainState(params=params, round=0, history=[])

    def run(self, state: TrainState, batches: Iterator, key, n_rounds: int,
            log_every: int = 10, eval_fn: Optional[Callable] = None,
            ckpt_every: int = 0) -> TrainState:
        gammas = self.rule.sequence(state.round + n_rounds)
        dim = sum(int(l.size) for l in jax.tree.leaves(state.params))
        comm_mbits = round_comm_bits(self.fed, dim) / 1e6
        fed = self.fed
        rng = (np.random.default_rng(fed.seed)
               if fed.sampling_S is not None else None)
        self.cohort_trace = []
        self.fault_trace = None
        fdrv = None
        if fed.faults is not None:
            # same driver + rng construction as the reference runtime, so a
            # (seed, model) pair produces the bit-identical FaultTrace on
            # either backend
            from ..faults import FaultDriver, fault_rng  # cycle
            fdrv = FaultDriver(fed.faults, fed.n_workers, fed.agg_weights)
            frng = fault_rng(fed.seed)
        # round metrics (repro.obs): reads only host-side values the loop
        # already computes; disabled runs pay one boolean check per round
        obs_on = _OBS_ON.on
        if obs_on:
            _htvar_h = _METRICS.histogram("run.ht_weight_var", backend="spmd")
            _bits_c = _METRICS.counter("run.wire_bits", backend="spmd",
                                       codec=fed.wire)
            _rounds_c = _METRICS.counter("run.rounds", backend="spmd")
        for r in range(state.round, state.round + n_rounds):
            key, rkey = jax.random.split(key)
            batch = next(batches)
            t0 = time.time()
            idx = pi = u = None
            if rng is not None:
                from ..sampling.base import cohort_weights, draw_cohort
                idx, pi = draw_cohort(rng, fed.n_workers, fed.sampling_S,
                                      fed.sampling_p)
                self.cohort_trace.append(idx)
            if fdrv is not None:
                u = fdrv.step(frng, r, idx, pi)
                # crashed workers never upload; timed-out/corrupt ones do
                # (the server just discards them), so they still pay bits
                rec = fdrv.last
                uploaded = [i for i in rec.cohort if i not in rec.crashed]
                comm_mbits = round_comm_bits(fed, dim, cohort=uploaded) / 1e6
            elif idx is not None:   # sampling only: the historical HT path
                u = cohort_weights(idx, pi, fed.n_workers, fed.agg_weights)
                comm_mbits = round_comm_bits(fed, dim, cohort=idx) / 1e6
            # the dispatch only: the round runs on asynchronously, and no
            # block_until_ready is added (observing must not serialize it)
            with _trace.span("trainer.round", round=r):
                if u is not None:
                    state.params, metrics = self._round(
                        state.params, batch, rkey, jnp.float32(gammas[r]),
                        jnp.asarray(u, jnp.float32))
                else:
                    state.params, metrics = self._round(
                        state.params, batch, rkey, jnp.float32(gammas[r]))
            if obs_on:
                _rounds_c.inc()
                _bits_c.inc(comm_mbits * 1e6)
                if u is not None:
                    # plain-python variance (see genqsgd.run): keeps the
                    # per-round observability cost off the ufunc path
                    _ul = u.tolist()
                    _mu = sum(_ul) / len(_ul)
                    _htvar_h.observe(
                        sum((v - _mu) ** 2 for v in _ul) / len(_ul))
            if r % log_every == 0 or r == state.round + n_rounds - 1:
                # the logged round's host sync
                with _trace.span("trainer.log", round=r):
                    rec = {"round": r, "gamma": float(gammas[r]),
                           "loss": float(metrics["loss"]),
                           "delta_norm": float(metrics["delta_norm"]),
                           "comm_mbits": comm_mbits,
                           "dt": time.time() - t0}
                if eval_fn is not None:
                    rec.update(eval_fn(state.params))
                state.history.append(rec)
                print("  " + " ".join(f"{k}={v:.4g}" if isinstance(v, float)
                                      else f"{k}={v}" for k, v in rec.items()),
                      flush=True)
            if self.ckpt_dir and ckpt_every and (r + 1) % ckpt_every == 0:
                CKPT.save(f"{self.ckpt_dir}/round_{r+1:06d}.ckpt",
                          state.params, {"round": r + 1})
            state.round = r + 1
        if fdrv is not None:
            self.fault_trace = fdrv.trace()
        return state
