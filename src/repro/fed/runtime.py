"""Distributed GenQSGD runtime: Algorithm 1 mapped onto the (fl, fsdp, tp) mesh.

One *round* = the body of Algorithm 1's global iteration:
  1. every fl worker group starts from the shared global model x̂,
  2. runs K_max local mini-batch SGD steps (workers with K_n < K_max do the
     paper's "virtual" masked updates, eqs. (6)-(8)),
  3. encodes its normalized model delta (x_n - x̂)/γ per tensor with its
     codec (Assumption 1 holds per tensor, hence for the concatenation with
     q = max_t q_t) — or per bucket of ``FedConfig.bucket`` coordinates
     (QSGD bucketing, matching what ``EdgeSystem(q_dim=...)`` prices),
  4. aggregation: the server mean of quantized deltas (5), re-quantized with
     the server codec and applied by every node (3).

The runtime splits the communication concern along the codec/transport axis
of :mod:`repro.compress`:

  * the *codec* (what is sent) is QSGD with per-worker ``s_n`` — possibly
    heterogeneous — or the identity (``s=None``), evaluated through the
    package's single level implementation (``compress.encode_tensor`` /
    ``decode_tensor``, traced-``s`` capable so heterogeneous workers
    vectorize through vmap);
  * the *transport* (how it travels) is ``FedConfig.wire``, one of
    ``compress.RUNTIME_WIRES``:

    wire="f32"   — paper-faithful math: quantized *values* travel as f32
                   (mean over fl => an XLA all-reduce of f32).
    wire="int8"  — QSGD levels travel as int8 via an explicit all-gather
                   inside shard_map; dequantize + average locally.  4x fewer
                   collective bytes on the fl (cross-pod) axis; bit-identical
                   results to "f32" (levels are exact integers in both).
    wire="int4"  — two levels packed per byte (``compress.pack_int4``) before
                   the all-gather: 8x fewer bytes than f32, 2x fewer than
                   int8, for the paper's low-s regime (s_n <= 7).  Packing is
                   lossless, so results stay bit-identical to "f32".
    wire="rs_ag" — reduce-scatter + all-gather decomposition of the f32 mean
                   (each fl member owns 1/fl of the delta): ~2x fewer wire
                   bytes than a ring all-reduce of the same payload, exact
                   f32 math.
    wire="elias" — QSGD levels Elias-omega gap-coded per worker
                   (:mod:`repro.compress.elias`, the paper's tighter M_s
                   bound).  Variable-length streams cannot ride SPMD
                   collectives, so this is a *reference* transport like
                   "f32": each worker's levels round-trip through the real
                   coder outside the shard_map, the realized stream
                   lengths land in ``metrics["elias_bits"]``, and the
                   aggregation math stays bit-identical to "f32" (the
                   coder is lossless on levels).

  The cost layer (:class:`repro.core.cost.EdgeSystem`) prices ``M_s`` through
  the same ``codec.wire_bits`` table, so the (K, B, s) the optimizer picks
  refer to exactly the bytes these transports move.

Local steps are vmapped over an explicit leading fl axis sharded P('fl', ...)
— GSPMD keeps each worker group's replica resident on its own (fsdp, tp)
sub-grid and the ONLY fl-axis traffic is the aggregation, exactly the paper's
communication pattern.
"""
from __future__ import annotations

import dataclasses
import functools
from typing import Callable, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding
from jax.sharding import PartitionSpec as P

from ..compress import (RUNTIME_WIRES, decode_tensor, elias, encode_tensor,
                        make_codec, pack_int4, unpack_int4, wire_max_s)
from ..configs.base import ArchConfig
from . import sharding as SH

__all__ = ["FedConfig", "make_round_fn"]


@dataclasses.dataclass(frozen=True)
class FedConfig:
    """Static GenQSGD runtime parameters for one training job."""
    n_workers: int                       # fl axis size
    Kn: tuple                            # per-worker local steps (len == fl)
    s0: Optional[int]                    # server quantizer (None = exact)
    sn: object = None                    # worker quantizer: int (homogeneous),
                                         # tuple of per-worker ints, or None
    wire: str = "f32"                    # one of compress.RUNTIME_WIRES
    bucket: object = None                # per-bucket-norm quantization: bucket
                                         # size (EdgeSystem's q_dim), or None
    aux_weight: float = 0.01
    microbatch: int = 1                  # grad-accumulation splits per local step
    agg_weights: object = None           # per-worker aggregation weights w_n
                                         # (tuple, len fl; None = plain mean)
    momentum: float = 0.0                # local-update momentum beta
    normalize: bool = False              # normalized local updates (GQFedWAvg)
    sampling_S: object = None            # per-round cohort size (None = full)
    sampling_p: object = None            # per-worker base probabilities
                                         # (tuple, len fl; None = uniform)
    seed: object = None                  # cohort/fault rng seed (trainer side)
    faults: object = None                # repro.faults.FaultSpec (None = no
                                         # faults — the historical path)

    def __post_init__(self):
        if self.wire not in RUNTIME_WIRES:
            raise ValueError(f"wire must be one of {RUNTIME_WIRES}, "
                             f"got {self.wire!r}")
        from ..families import check_agg_weights, check_momentum  # cycle
        if self.agg_weights is not None:
            object.__setattr__(self, "agg_weights",
                               check_agg_weights(self.agg_weights,
                                                 self.n_workers))
        check_momentum(self.momentum)
        if self.sampling_p is not None and self.sampling_S is None:
            raise ValueError("sampling_p given without sampling_S")
        if self.sampling_S is not None:
            from ..sampling.base import check_probs  # cycle
            S = int(self.sampling_S)
            if not 1 <= S <= self.n_workers:
                raise ValueError(
                    f"sampling_S={S} outside [1, N={self.n_workers}]")
            object.__setattr__(self, "sampling_S", S)
            if self.sampling_p is not None:
                p = check_probs(self.sampling_p, self.n_workers)
                if S * max(p) > 1.0 + 1e-9:
                    raise ValueError(
                        f"inclusion probability S*max(p)={S * max(p):.4g} "
                        f"exceeds 1")
                object.__setattr__(self, "sampling_p", p)
            # the per-round HT weight vector u is a traced round input, so
            # sampling needs an aggregation that runs OUTSIDE shard_map:
            # the f32 transport, or the bucketed level wires (whose decode
            # + combine already run on logical-global arrays).
            if not (self.wire == "f32"
                    or (self.bucket is not None
                        and self.wire in ("int8", "int4"))):
                raise ValueError(
                    f"client sampling is not supported on wire="
                    f"{self.wire!r}" + ("" if self.bucket is not None
                                        else " without bucketing")
                    + "; use wire='f32' or a bucketed int8/int4 wire")
        if self.faults is not None:
            from ..faults import FaultSpec  # cycle
            if not isinstance(self.faults, FaultSpec):
                raise TypeError(f"faults must be a repro.faults.FaultSpec, "
                                f"got {type(self.faults)}")
            if self.faults.N != self.n_workers:
                raise ValueError(f"FaultSpec describes {self.faults.N} "
                                 f"workers, config has {self.n_workers}")
            # deadline-HT aggregation rides the same traced per-round u
            # vector as client sampling, with the same wire restriction
            if not (self.wire == "f32"
                    or (self.bucket is not None
                        and self.wire in ("int8", "int4"))):
                raise ValueError(
                    f"fault injection is not supported on wire="
                    f"{self.wire!r}" + ("" if self.bucket is not None
                                        else " without bucketing")
                    + "; use wire='f32' or a bucketed int8/int4 wire")
        if self.bucket is not None and int(self.bucket) <= 0:
            raise ValueError(f"bucket must be positive, got {self.bucket}")
        cap = wire_max_s(self.wire)
        if self.wire == "elias":
            # pricing is unbounded in s (cap is None), but the runtime
            # coder reads levels from an int8 container like every other
            # level transport
            cap = elias.MAX_RUNTIME_S
        for s in self.sn_tuple() + (self.s0,):
            if s is not None and cap is not None and s > cap:
                raise ValueError(
                    f"wire {self.wire!r} carries s <= {cap}, got {s}")
        sn = self.sn_tuple()
        if not self.sn_exact and any(s is None for s in sn):
            # the level transports carry every worker's delta in the same
            # integer container, which cannot represent an exact passthrough
            raise ValueError("mixed exact (s=None) and quantized workers are "
                             "not supported: set s_n for every worker, or "
                             "None for all")
        if self.wire == "int4" and self.sn_exact:
            raise ValueError("int4 wire packs quantized levels; exact "
                             "(s=None) workers need the f32 or rs_ag wire")

    @property
    def K_max(self) -> int:
        return int(max(self.Kn))

    def sn_tuple(self) -> tuple:
        """Per-worker quantization parameters (heterogeneous allowed)."""
        if isinstance(self.sn, (tuple, list)):
            assert len(self.sn) == self.n_workers
            return tuple(self.sn)
        return (self.sn,) * self.n_workers

    @property
    def sn_exact(self) -> bool:
        return all(s is None for s in self.sn_tuple())

    def codecs(self) -> tuple:
        """Per-worker codec views (cost accounting / introspection)."""
        return tuple(make_codec(s, wire=self.wire, bucket=self.bucket)
                     for s in self.sn_tuple())

    def server_codec(self):
        """An exact server multicast (s0=None) is raw f32 regardless of the
        worker wire — the packing wire can't carry it, but the runtime never
        packs the server update anyway."""
        wire = self.wire if self.s0 is not None else "f32"
        return make_codec(self.s0, wire=wire, bucket=self.bucket)


# ---------------------------------------------------------------------------
# counter-based uniform noise (murmur3 finalizer) — jax.random's threefry
# emits reshape/concat patterns GSPMD cannot partition (measured: full f32
# noise tensors replicated per device at 405B scale), so quantization noise
# comes from a pure elementwise index hash instead.  Avalanche quality is
# ample for stochastic rounding; uniformity/unbiasedness are unit-tested.
# ---------------------------------------------------------------------------
def _mix32(z: jax.Array) -> jax.Array:
    z = (z ^ (z >> 16)) * jnp.uint32(0x85EBCA6B)
    z = (z ^ (z >> 13)) * jnp.uint32(0xC2B2AE35)
    return z ^ (z >> 16)


def uniform_like(x: jax.Array, seed: jax.Array) -> jax.Array:
    """U(0,1) f32 tensor shaped like x, from a counter hash (partitionable)."""
    n = int(np.prod(x.shape)) if x.shape else 1
    idx = jnp.arange(n, dtype=jnp.uint32).reshape(x.shape)
    z = idx * jnp.uint32(0x9E3779B9) + seed.astype(jnp.uint32)
    z = _mix32(_mix32(z) + jnp.uint32(0x27D4EB2F))
    return (z >> jnp.uint32(8)).astype(jnp.float32) * jnp.float32(1.0 / (1 << 24))


def _seed_from(key: jax.Array, salt: int) -> jax.Array:
    data = jax.random.key_data(key) if jnp.issubdtype(key.dtype, jax.dtypes.prng_key) \
        else key
    words = data.reshape(-1).astype(jnp.uint32)
    seed = jnp.uint32(salt * 0x9E3779B9 & 0xFFFFFFFF)
    for i in range(words.shape[0]):
        seed = _mix32(seed ^ words[i])
    return seed


# ---------------------------------------------------------------------------
# round function
# ---------------------------------------------------------------------------
def make_round_fn(api, cfg: ArchConfig, fed: FedConfig, mesh: Mesh,
                  fsdp_weights: bool = True, moe_tp_only: bool = False):
    """Build genqsgd_round(x_hat, batch, noise_key) -> (x_hat', metrics).

    x_hat: param pytree sharded (fsdp, tp), replicated over fl.
    batch: leaves (fl, K_max, B_local, ...), sharded P('fl', None, 'fsdp', ...).
    The round's first stage, ``genqsgd_round.local_steps(x_hat, batch,
    gamma)``, is exposed so a caller can see where the workers' replicas
    live.  The round's phases run under the ``jax.named_scope``s
    ``local_steps``, ``worker_encode``, ``aggregate`` and ``server_update``,
    which a profiler trace shows in each operation's name stack.
    """
    Kn = jnp.asarray(fed.Kn, jnp.int32)

    def _grad_sharding(tree):
        """Pin weight-gradient shardings to the param layout — otherwise the
        partitioner materializes full unsharded f32 dW tensors and all-reduces
        them (measured 7 x 3.25 GiB concurrent at 405B) instead of
        reduce-scattering."""
        specs = SH.param_specs(tree, mesh, fsdp_weights,
                               moe_tp_only=moe_tp_only)
        return jax.tree.map(
            lambda g, sp: jax.lax.with_sharding_constraint(
                g, NamedSharding(mesh, sp)), tree, specs)

    use_momentum = fed.momentum > 0.0 or fed.normalize
    beta = jnp.float32(fed.momentum)

    def local_train(x_hat, data, kn, gamma):
        def loss_grad(pp, micro):
            l, g = jax.value_and_grad(
                lambda q: api.loss_train(q, cfg, micro,
                                         aux_weight=fed.aux_weight))(pp)
            return l, _grad_sharding(g)

        def eval_grad(p, batch_k):
            # mixed precision: forward/backward in bf16 against a bf16 view,
            # the update applied to the (possibly f32) master copy.
            p_half = jax.tree.map(
                lambda w: w.astype(jnp.bfloat16)
                if w.dtype == jnp.float32 else w, p)
            M = fed.microbatch
            if M > 1:
                # grad accumulation: activations scale with B/M, not B
                micro_tree = jax.tree.map(
                    lambda a: a.reshape((M, a.shape[0] // M) + a.shape[1:])
                    if a.ndim >= 1 and a.shape[0] % M == 0
                    else jnp.broadcast_to(a, (M,) + a.shape), batch_k)
                if "positions3" in batch_k:  # (3, B, S) -> split on B
                    micro_tree["positions3"] = jnp.moveaxis(
                        batch_k["positions3"].reshape(
                            3, M, batch_k["positions3"].shape[1] // M, -1),
                        1, 0)

                def acc_body(acc, micro):
                    g_acc, l_acc = acc
                    l, g = loss_grad(p_half, micro)
                    g_acc = jax.tree.map(
                        lambda a, gg: a + gg.astype(a.dtype) / M, g_acc, g)
                    return (g_acc, l_acc + l / M), None

                zeros = jax.tree.map(
                    lambda w: jnp.zeros(w.shape, w.dtype), p_half)
                (g, loss), _ = jax.lax.scan(acc_body,
                                            (zeros, jnp.zeros(())),
                                            micro_tree)
            else:
                loss, g = loss_grad(p_half, batch_k)
            return loss, g

        def body(carry, inp):
            p, step = carry
            loss, g = eval_grad(p, inp)
            active = (step < kn).astype(jnp.float32)
            p = jax.tree.map(
                lambda w, gg: (w.astype(jnp.float32)
                               - (gamma * active) * gg.astype(jnp.float32)
                               ).astype(w.dtype), p, g)
            return (p, step + 1), loss

        def body_momentum(carry, inp):
            # GQFedWAvg local update: v ← β v + (1-β) g on active steps,
            # move along v (unit-normalized over the whole model when
            # fed.normalize); virtual steps leave both x and v untouched.
            p, v, step = carry
            loss, g = eval_grad(p, inp)
            active = (step < kn).astype(jnp.float32)
            v = jax.tree.map(
                lambda vv, gg: vv + active * (beta * vv + (1.0 - beta)
                                              * gg.astype(jnp.float32) - vv),
                v, g)
            if fed.normalize:
                vn = jnp.sqrt(sum(jnp.sum(jnp.square(l))
                                  for l in jax.tree.leaves(v)))
                scale = (gamma * active) / jnp.maximum(vn, 1e-12)
            else:
                scale = gamma * active
            p = jax.tree.map(
                lambda w, vv: (w.astype(jnp.float32) - scale * vv)
                .astype(w.dtype), p, v)
            return (p, v, step + 1), loss

        if use_momentum:
            v0 = jax.tree.map(lambda w: jnp.zeros(w.shape, jnp.float32),
                              x_hat)
            (p, _, _), losses = jax.lax.scan(
                body_momentum, (x_hat, v0, jnp.int32(0)), data)
        else:
            (p, _), losses = jax.lax.scan(body, (x_hat, jnp.int32(0)), data)
        return p, losses.mean()

    sn_arr = (None if fed.sn_exact
              else jnp.asarray([s or 0 for s in fed.sn_tuple()], jnp.float32))

    bucket = None if fed.bucket is None else int(fed.bucket)

    w_agg = None
    if fed.agg_weights is not None:
        _w = np.asarray(fed.agg_weights, np.float64)
        w_agg = jnp.asarray(_w / _w.sum(), jnp.float32)

    def combine_fl(d, u=None):
        """Collapse a (fl, ...) stacked leaf: the server mean, the family's
        general weighted aggregation (sum_n w_n d_n), or — under client
        sampling — the round's Horvitz-Thompson sum ``sum_n u_n d_n``
        (``u`` already folds the cohort mask, the aggregation weights and
        the 1/pi_n reweighting, so it replaces both other branches)."""
        if u is not None:
            return jnp.tensordot(u.astype(jnp.float32), d, axes=1)
        if w_agg is None:
            return d.mean(axis=0)
        return jnp.tensordot(w_agg, d, axes=1)

    def worker_quantize(delta, key, s_w):
        leaves, treedef = jax.tree.flatten(delta)
        lvls, norms = [], []
        for i, leaf in enumerate(leaves):
            u = uniform_like(leaf, _seed_from(key, i))
            lvl, nrm = encode_tensor(leaf, None if sn_arr is None else s_w,
                                     u, bucket=bucket)
            lvls.append(lvl)
            norms.append(nrm)
        return (jax.tree.unflatten(treedef, lvls),
                jax.tree.unflatten(treedef, norms))

    # -- aggregation ---------------------------------------------------------
    def _decode_fl(levels_fl, norms_fl):
        """Per-worker dequantize of (fl, ...) stacked leaves — plain GSPMD
        ops on logical-global arrays (bucket boundaries index *global*
        coordinates, so bucketed decode must not run on shard-local blocks)."""
        ss = jnp.zeros(fed.n_workers) if sn_arr is None else sn_arr
        return jax.tree.map(
            lambda l, n: jax.vmap(
                lambda li, ni, si: decode_tensor(
                    li, ni, None if sn_arr is None else si, bucket=bucket))(
                l, n, ss),
            levels_fl, norms_fl)

    def agg_f32(levels_fl, norms_fl, u=None):
        """Paper-faithful: dequantize then mean over fl (f32 all-reduce);
        weighted families aggregate sum_n w_n Q(Δ_n) instead, sampled
        rounds the HT-weighted cohort sum."""
        return jax.tree.map(lambda d: combine_fl(d, u),
                            _decode_fl(levels_fl, norms_fl))

    def _replicated(x):
        return jax.lax.with_sharding_constraint(x, NamedSharding(mesh, P()))

    def _elias_roundtrip(levels_fl):
        """Reference elias transport: round-trip every worker's levels
        through the omega gap coder (lossless, so aggregation stays
        bit-identical to the f32 transport) and account the realized
        stream bits.  Each worker's *whole flattened delta* is one stream
        — exactly the d-dimensional message ``EdgeSystem.M_s`` prices, and
        one sequential decode per worker instead of one per tensor.  Runs
        on logical-global arrays outside shard_map — variable-length
        streams cannot ride SPMD collectives.  The stream and decoded
        levels are pinned fully replicated: left to itself the
        partitioner shards the decode scan's d-length outputs, turning
        every sequential step into cross-device traffic."""
        leaves, treedef = jax.tree.flatten(levels_fl)
        flat = _replicated(jnp.concatenate(
            [l.reshape(fed.n_workers, -1) for l in leaves],
            axis=1).astype(jnp.int8))
        words, nb = jax.vmap(elias.encode_levels)(flat)
        dec = _replicated(jax.vmap(
            lambda w: elias.decode_levels(w, flat.shape[1]))(
                _replicated(words)))
        out, off = [], 0
        for l in leaves:
            n = l.size // fed.n_workers
            out.append(dec[:, off:off + n].reshape(l.shape).astype(l.dtype))
            off += n
        return jax.tree.unflatten(treedef, out), jnp.sum(nb)

    def _agg_rs_ag_local(levels_loc, norms_loc):
        """Runs inside shard_map: dequantize locally (whole-tensor norms
        only — see :func:`_decode_fl` for why bucketed decode can't run on
        shard-local blocks), reduce-scatter the f32 mean over fl (each
        member owns a 1/fl shard), then all-gather — ~2x fewer wire bytes
        than a ring all-reduce of the same payload."""
        my_s = (None if sn_arr is None
                else sn_arr[jax.lax.axis_index("fl")])
        deq = jax.tree.map(
            lambda lvl, nrm: decode_tensor(lvl, nrm[0], my_s),
            levels_loc, norms_loc)
        return _mean_rs_ag_local(deq)

    def _mean_rs_ag_local(deq_loc):
        """Runs inside shard_map: mean (or weighted sum) of per-worker f32
        deltas over fl via reduce-scatter + all-gather.  ``deq_loc`` leaves
        are the local (1, ...) fl blocks of already-decoded deltas; each
        member pre-scales its own block (1/n, or its aggregation weight) so
        the reduction is a plain sum either way."""
        n = fed.n_workers

        def per_leaf(d):
            if w_agg is None:
                d = d[0] / n
            else:
                d = d[0] * w_agg[jax.lax.axis_index("fl")]
            if d.size % n:  # ragged leaf: fall back to psum
                return jax.lax.psum(d, "fl")
            own = jax.lax.psum_scatter(d.reshape(n, -1), "fl",
                                       scatter_dimension=0, tiled=False)
            return jax.lax.all_gather(own, "fl").reshape(d.shape)

        return jax.tree.map(per_leaf, deq_loc)

    def _agg_levels_local(levels_loc, norms_loc, pack_nibbles=False):
        """Runs inside shard_map: all-gather the level payload over fl,
        dequantize and average locally (whole-tensor norms only).  With
        ``pack_nibbles`` two levels travel per byte (half the int8 wire
        bytes); packing is lossless for s <= 7, so the result stays
        bit-identical to the f32 transport."""
        def per_leaf(lvl, nrm):
            # lvl: (1, ...) local block; gather -> (fl, ...)
            payload = pack_int4(lvl[0]) if pack_nibbles else lvl[0]
            g = jax.lax.all_gather(payload, "fl")         # int8 on the wire
            gn = jax.lax.all_gather(nrm[0], "fl")
            ss = (jnp.zeros(fed.n_workers) if sn_arr is None else sn_arr)

            def dec(pi, ni, si):
                li = (unpack_int4(pi, lvl[0].size).reshape(lvl[0].shape)
                      if pack_nibbles else pi)
                return decode_tensor(li, ni, None if sn_arr is None else si)

            return combine_fl(jax.vmap(dec)(g, gn, ss))
        return jax.tree.map(per_leaf, levels_loc, norms_loc)

    def _agg_int8_local(levels_loc, norms_loc):
        return _agg_levels_local(levels_loc, norms_loc)

    def _agg_int4_local(levels_loc, norms_loc):
        return _agg_levels_local(levels_loc, norms_loc, pack_nibbles=True)

    def _gather_levels_local(levels_loc, pack_nibbles=False):
        """Runs inside shard_map: move ONLY the compact level payload over fl
        (raw int8 or packed int4 on the wire) and return the gathered
        (fl, ...) levels.  Used by the bucketed transports, whose dequantize
        runs outside the shard_map (see :func:`_decode_fl`)."""
        def per_leaf(lvl):
            payload = pack_int4(lvl[0]) if pack_nibbles else lvl[0]
            g = jax.lax.all_gather(payload, "fl")         # int8 on the wire
            if pack_nibbles:
                g = jax.vmap(lambda pi: unpack_int4(pi, lvl[0].size)
                             .reshape(lvl[0].shape))(g)
            return g
        return jax.tree.map(per_leaf, levels_loc)

    def _pspecs(x_hat_example):
        return SH.param_specs(x_hat_example, mesh, fsdp_weights,
                              moe_tp_only=moe_tp_only)

    def make_agg_sm(x_hat_example, body):
        pspecs = _pspecs(x_hat_example)
        lv_specs = SH.with_fl(pspecs)
        nm_specs = jax.tree.map(lambda _: P("fl"), pspecs,
                                is_leaf=lambda x: isinstance(x, P))
        return jax.shard_map(body, mesh=mesh,
                             in_specs=(lv_specs, nm_specs), out_specs=pspecs,
                             check_vma=False)

    def make_gather_sm(x_hat_example, pack_nibbles):
        pspecs = _pspecs(x_hat_example)
        out_specs = jax.tree.map(lambda s: P(None, *s), pspecs,
                                 is_leaf=lambda x: isinstance(x, P))
        return jax.shard_map(
            functools.partial(_gather_levels_local,
                              pack_nibbles=pack_nibbles),
            mesh=mesh, in_specs=(SH.with_fl(pspecs),), out_specs=out_specs,
            check_vma=False)

    def make_mean_sm(x_hat_example):
        pspecs = _pspecs(x_hat_example)
        return jax.shard_map(_mean_rs_ag_local, mesh=mesh,
                             in_specs=(SH.with_fl(pspecs),), out_specs=pspecs,
                             check_vma=False)

    # -- the round ----------------------------------------------------------
    def local_steps(x_hat, batch, gamma):
        """Every worker's K_max local steps -> ((fl, ...) params, (fl,)
        losses).  ``spmd_axis_name`` carries the fl axis into the
        gradient-sharding constraints, so worker n's replica and gradients
        stay on its own (fsdp, tp) sub-grid instead of being replicated
        over fl."""
        return jax.vmap(local_train, in_axes=(None, 0, 0, None),
                        spmd_axis_name="fl")(x_hat, batch, Kn, gamma)

    def genqsgd_round(x_hat, batch, key, gamma, u=None):
        keys = jax.random.split(key, fed.n_workers + 1)
        wkeys, skey = keys[:-1], keys[-1]

        with jax.named_scope("local_steps"):
            params_w, losses = local_steps(x_hat, batch, gamma)

        with jax.named_scope("worker_encode"):
            # (5): normalized per-worker deltas, quantized per tensor
            deltas = jax.tree.map(
                lambda pw, xh: (pw - xh[None]) / gamma, params_w, x_hat)
            s_dummy = (jnp.zeros(fed.n_workers) if sn_arr is None else sn_arr)
            levels_fl, norms_fl = jax.vmap(worker_quantize)(deltas, wkeys,
                                                            s_dummy)

        with jax.named_scope("aggregate"):
            elias_bits = None
            if fed.wire == "f32":
                delta_hat = agg_f32(levels_fl, norms_fl, u)
            elif fed.wire == "elias":
                # exact workers (s=None) ride raw f32, exactly as priced
                if not fed.sn_exact:
                    levels_fl, elias_bits = _elias_roundtrip(levels_fl)
                delta_hat = agg_f32(levels_fl, norms_fl, u)
            elif bucket is None:
                body = {"int8": _agg_int8_local, "int4": _agg_int4_local,
                        "rs_ag": _agg_rs_ag_local}[fed.wire]
                delta_hat = make_agg_sm(x_hat, body)(levels_fl, norms_fl)
            elif fed.wire in ("int8", "int4"):
                # bucketed level wires: compact payload moves inside shard_map,
                # dequantize outside on logical-global arrays (no further
                # fl-axis traffic — the gathered levels are fl-replicated).
                # Unlike the per-tensor paths, cross-wire agreement here is
                # ulp-level, not bitwise: the decode sits in a different fusion
                # context, so XLA's FMA choices can flip a few stochastic
                # roundings upstream.
                g = make_gather_sm(x_hat, fed.wire == "int4")(levels_fl)
                delta_hat = jax.tree.map(lambda d: combine_fl(d, u),
                                         _decode_fl(g, norms_fl))
            else:  # bucketed rs_ag: decode per worker, then rs+ag the f32 mean
                delta_hat = make_mean_sm(x_hat)(
                    _decode_fl(levels_fl, norms_fl))

        with jax.named_scope("server_update"):
            # (3): server quantization of the averaged update, applied
            # everywhere
            leaves, treedef = jax.tree.flatten(delta_hat)
            new_leaves = []
            lvls, nrms = [], []
            for i, leaf in enumerate(leaves):
                u = uniform_like(leaf, _seed_from(skey, 1000 + i))
                lvl, nrm = encode_tensor(leaf, fed.s0, u, bucket=bucket)
                lvls.append(lvl)
                nrms.append(nrm)
            if fed.wire == "elias" and fed.s0 is not None:
                # the server multicast rides the same coder: one stream over
                # the whole flattened update (lossless on levels)
                flat = _replicated(jnp.concatenate(
                    [l.reshape(-1) for l in lvls]).astype(jnp.int8))
                words, nb = elias.encode_levels(flat)
                dec = _replicated(elias.decode_levels(_replicated(words),
                                                      flat.size))
                off = 0
                for i, l in enumerate(lvls):
                    lvls[i] = (dec[off:off + l.size].reshape(l.shape)
                               .astype(l.dtype))
                    off += l.size
                elias_bits = (nb if elias_bits is None else elias_bits + nb)
            for leaf_l, leaf_n, xh in zip(lvls, nrms, jax.tree.leaves(x_hat)):
                dq = decode_tensor(leaf_l, leaf_n, fed.s0, bucket=bucket)
                new_leaves.append((xh.astype(jnp.float32)
                                   + gamma * dq).astype(xh.dtype))
            x_new = jax.tree.unflatten(treedef, new_leaves)
        metrics = {"loss": losses.mean(),
                   "delta_norm": jnp.sqrt(sum(
                       jnp.sum(jnp.square(l.astype(jnp.float32)))
                       for l in leaves))}
        if elias_bits is not None:
            metrics["elias_bits"] = elias_bits
        return x_new, metrics

    genqsgd_round.local_steps = local_steps
    return genqsgd_round
