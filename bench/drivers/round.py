"""Driver ``round``: GenQSGD federated rounds through ``GenQSGDTrainer.run``.

Set-up makes the weights and a pool of batches on the device from the
seed, builds the trainer (one compiled round program with its state) and
drives it through its first ``check_rounds`` rounds, one ``run`` call per
round, recording each round's loss and how far the parameters moved.  That
same trainer and state then run the window: ``run`` calls of
``rounds_per_call`` rounds until ``--seconds`` have passed, ending in
``block_until_ready`` on the last round's parameters.

After the window, with the program's state freed, the plain reference
(``bench/reference/qwen3.py``) replays the first rounds from the same
weights, batches and round keys, and the check compares, for each round,
the mean local loss, and per parameter leaf the norm of the first round's
update and of the change after ``check_rounds`` rounds.

Traffic keys: ``workers``, ``local_steps``, ``batch``, ``seq``, ``wire``,
``s0``, ``sn``, ``gamma``, ``pool`` (batches made at set-up, cycled),
``rounds_per_call``, ``check_rounds``, ``limits``.
"""
from __future__ import annotations

import gc
import itertools
import time

import numpy as np

from bench.lib.harness import Check, Outcome
from bench.reference import qwen3 as REF


def arch_config(config: dict):
    """The program's ArchConfig for a configuration file's ``model``."""
    from repro.configs.base import ArchConfig

    m = config["model"]
    s = REF.Shapes.from_hf(m)
    return ArchConfig(
        name=config["name"], family="dense", citation=config["source"],
        n_layers=s.n_layers, d_model=s.d_model, n_heads=s.n_heads,
        n_kv=s.n_kv, d_ff=s.d_ff, vocab=s.vocab, d_head=s.d_head,
        pattern=("attn",), qk_norm=True, rope_theta=s.rope_theta,
        norm_eps=s.norm_eps, tie_embeddings=True)


def gap_by_leaf(got, ref, keep):
    """Worst leaf's |norm_got - norm_ref| / max(norm_ref, median norm_ref)
    over the leaves ``keep`` marks."""
    got, ref = np.asarray(got, np.float64), np.asarray(ref, np.float64)
    med = float(np.median(ref[keep]))
    return float(np.max(np.abs(got - ref)[keep]
                        / np.maximum(ref[keep], med)))


class Job:
    """What the window runs, and how its first rounds are re-made for the
    reference.  Tests subclass it to plant faults."""

    def __init__(self, run):
        import jax
        import jax.numpy as jnp
        from jax.sharding import Mesh

        from repro.fed import sharding as SH
        from repro.models.registry import model_api

        self.run = run
        t = run.traffic
        self.shapes = REF.Shapes.from_hf(run.config["model"])
        self.cfg = arch_config(run.config)
        self.api = model_api(self.cfg)
        self.W, self.K = int(t["workers"]), int(t["local_steps"])
        self.B, self.S = int(t["batch"]), int(t["seq"])
        devs = run.devices()[:self.W] if run.on_chip else jax.devices()[:1]
        self.mesh = Mesh(np.array(devs).reshape(len(devs), 1, 1),
                         ("fl", "fsdp", "tp"))
        abstract = jax.eval_shape(lambda k: REF.init_params(k, self.shapes),
                                  jax.random.PRNGKey(0))
        program = jax.eval_shape(lambda k: self.api.init_params(k, self.cfg),
                                 jax.random.PRNGKey(0))
        if (jax.tree.structure(abstract) != jax.tree.structure(program)
                or [a.shape for a in jax.tree.leaves(abstract)]
                != [a.shape for a in jax.tree.leaves(program)]):
            raise ValueError("the reference's weights are not laid out as "
                             "the program's")
        self.param_sh = SH.param_shardings(abstract, self.mesh)
        self._init = jax.jit(REF.init_params, static_argnums=1,
                             out_shardings=self.param_sh)
        shape = (int(t["pool"]), self.W, self.K, self.B, self.S + 1)
        self._tokens = jax.jit(lambda k: jax.random.randint(
            k, shape, 0, self.shapes.vocab, jnp.int32), static_argnums=())
        example = {"tokens": jax.ShapeDtypeStruct(shape[1:-1] + (self.S,),
                                                  jnp.int32)}
        self.batch_sh = SH.shardings(SH.batch_specs(
            {"tokens": example["tokens"], "labels": example["tokens"]},
            self.mesh, "fl_train"), self.mesh)
        self.norms = jax.jit(REF.leaf_norms)
        self._norms_from_init = jax.jit(lambda x, key: REF.leaf_norms(
            x, REF.init_params(key, self.shapes)))

    def change_norms(self, x):
        """Per-leaf norms of x - the seed's weights, the weights made anew
        inside the program that reads them (no second copy is held)."""
        return [float(v) for v in self._norms_from_init(x, self.run.key(1))]

    def weights(self):
        return self._init(self.run.key(1), self.shapes)

    def batches(self):
        """The pool, as the window's feed will cycle it."""
        import jax

        toks = self._tokens(self.run.key(2))
        return [jax.device_put({"tokens": toks[i, ..., :-1],
                                "labels": toks[i, ..., 1:]}, self.batch_sh)
                for i in range(toks.shape[0])]

    def round_key(self, r: int):
        return self.run.key(3, r)

    def trainer(self):
        from repro.core.step_rules import ConstantRule
        from repro.fed.runtime import FedConfig
        from repro.train.trainer import GenQSGDTrainer

        t = self.run.traffic
        fed = FedConfig(n_workers=self.W, Kn=(self.K,) * self.W,
                        s0=int(t["s0"]), sn=int(t["sn"]), wire=t["wire"])
        return GenQSGDTrainer(self.api, self.cfg, fed, self.mesh,
                              step_rule=ConstantRule(float(t["gamma"])))


def run(run, job_cls=Job) -> Outcome:
    import jax

    from repro.train.trainer import TrainState

    t = run.traffic
    job = job_cls(run)
    n_check = int(t["check_rounds"])
    tokens_per_round = job.W * job.K * job.B * job.S

    # -- set-up: one trainer, driven through its first rounds ---------------
    trainer = job.trainer()
    x0 = job.weights()
    state = TrainState(params=x0, round=0, history=[])
    feed = itertools.cycle(job.batches())
    losses, first_update = [], None
    for r in range(n_check):
        state = trainer.run(state, feed, job.round_key(r), n_rounds=1,
                            log_every=1)
        losses.append(state.history[-1]["loss"])
        if r == 0:
            first_update = [float(v) for v in job.norms(state.params, x0)]
            del x0
    change = job.change_norms(state.params)

    # -- the window ----------------------------------------------------------
    per_call = int(t["rounds_per_call"])
    rounds = 0
    with run.window():
        start = time.perf_counter()
        while time.perf_counter() - start < run.seconds:
            with run.span("trainer.run"):
                state = trainer.run(state, feed, run.key(4, rounds),
                                    n_rounds=per_call, log_every=10**9)
            rounds += per_call
            # the trainer returns with rounds still queued on the device
            run.between(lambda: jax.block_until_ready(state.params))
        jax.block_until_ready(state.params)
    tokens_per_s = rounds * tokens_per_round / run.window_s
    final_loss = state.history[-1]["loss"]
    run.note(f"{rounds} rounds of {tokens_per_round} tokens in the window, "
             f"loss {losses[0]:.6f} -> {final_loss:.6f}")

    # -- the check: the reference replays the first rounds --------------------
    del state, trainer, feed
    gc.collect()
    ref = reference_rounds(job, n_check)
    keep = ref["grad_norms"] >= 1e-3 * np.median(ref["grad_norms"])
    loss_gap = max(abs(a - b) / abs(b) for a, b in zip(losses, ref["losses"]))
    lim = t["limits"]
    checks = [
        Check("loss_gap", loss_gap, lim["loss_gap"]),
        Check("update_gap", gap_by_leaf(first_update, ref["first_update"],
                                        keep), lim["update_gap"]),
        Check("change_gap", gap_by_leaf(change, ref["change"], keep),
              lim["change_gap"]),
    ]
    run.note(f"losses {losses} vs reference {ref['losses']}; "
             f"{int(np.sum(~keep))} leaves left out by the gradient rule")
    e2e = {"train_tokens_per_s": tokens_per_s,
           "peak_hbm_bytes": float(run.memory_peak_bytes or 0)}
    return Outcome(attempted=n_check, failed=0, end_to_end=e2e,
                   checks=checks,
                   counters={"rounds": rounds,
                             "tokens_per_round": tokens_per_round,
                             "seq": job.S})


def reference_rounds(job, n_check: int, matmul_dtype=None, rows=None) -> dict:
    """The plain reference over the first ``n_check`` rounds, from the
    same seed-made weights, batches and round keys."""
    import jax

    t = job.run.traffic
    x = job.weights()
    pool = job.batches()
    losses = []
    first_update = grad_norms = None
    for r in range(n_check):
        b = pool[r % len(pool)]
        rkey = jax.random.split(job.round_key(r))[1]
        x_new, loss, p1 = REF.round_ref(
            x, b["tokens"], b["labels"], rkey, float(t["gamma"]),
            int(t["s0"]), int(t["sn"]), job.shapes, matmul_dtype, rows)
        losses.append(float(loss))
        if r == 0:
            first_update = np.array([float(v) for v in job.norms(x_new, x)])
            # the first local step's gradient, by leaf: (x - p1) / gamma
            grad_norms = np.array([float(v) for v in job.norms(x, p1)]) \
                / float(t["gamma"])
        del p1
        x = x_new
    change = np.array(job.change_norms(x))
    return {"losses": losses, "first_update": first_update,
            "change": change, "grad_norms": grad_norms}
