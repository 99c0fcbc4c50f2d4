"""The round's named phases: ``fed/runtime.py`` wraps local steps, the
worker encode, the aggregation and the server update in
``jax.named_scope``s, which change only the operations' metadata."""
import contextlib

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from jax.sharding import Mesh

from repro.fed import sharding as SH
from repro.fed.runtime import FedConfig, make_round_fn
from repro.models.registry import get_config, model_api

PHASES = ("local_steps", "worker_encode", "aggregate", "server_update")


@pytest.fixture(scope="module")
def setup():
    mesh = Mesh(np.array(jax.devices()[:1]).reshape(1, 1, 1),
                ("fl", "fsdp", "tp"))
    cfg = get_config("qwen3-1.7b", smoke=True)
    api = model_api(cfg)
    key = jax.random.PRNGKey(0)
    params = api.init_params(key, cfg)
    params = jax.device_put(params, SH.param_shardings(params, mesh))
    toks = jax.random.randint(key, (1, 2, 2, 17), 0, cfg.vocab)
    batch = {"tokens": toks[..., :-1], "labels": toks[..., 1:]}
    return mesh, cfg, api, params, batch


@pytest.mark.parametrize("wire", ["f32", "int8", "elias"])
def test_round_bit_identical_without_scopes(setup, wire, monkeypatch):
    mesh, cfg, api, params, batch = setup
    fed = FedConfig(n_workers=1, Kn=(2,), s0=127, sn=127, wire=wire)
    args = (params, batch, jax.random.PRNGKey(1), jnp.float32(0.05))

    scoped = jax.jit(make_round_fn(api, cfg, fed, mesh))
    text = scoped.lower(*args).compile().as_text()
    for name in PHASES:
        assert f"/{name}/" in text, name
    x_scoped, m_scoped = scoped(*args)

    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    plain = jax.jit(make_round_fn(api, cfg, fed, mesh))
    assert "/local_steps/" not in plain.lower(*args).compile().as_text()
    x_plain, m_plain = plain(*args)

    for a, b in zip(jax.tree.leaves(x_scoped), jax.tree.leaves(x_plain)):
        assert np.array_equal(np.asarray(a), np.asarray(b))
    assert float(m_scoped["loss"]) == float(m_plain["loss"])
