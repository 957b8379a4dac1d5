"""Sharding/parallelism tests on the 8-device virtual CPU mesh."""

from __future__ import annotations

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from gofr_tpu.models.registry import get_model
from gofr_tpu.models.transformer import (
    init_transformer,
    transformer_forward,
    transformer_param_specs,
)
from gofr_tpu.parallel import make_mesh, make_train_step, mesh_axis_sizes, shard_pytree


def test_mesh_construction():
    mesh = make_mesh({"dp": 2, "tp": 4})
    assert mesh_axis_sizes(mesh) == {"dp": 2, "tp": 4}
    with pytest.raises(ValueError):
        make_mesh({"dp": 64, "tp": 4})


def test_sharded_params_match_replicated_forward():
    """tp-sharded forward must equal single-device forward (f32 so the
    comparison is tight; bf16 differs only by collective reduction order)."""
    import dataclasses

    import jax.numpy as jnp

    cfg = dataclasses.replace(get_model("llama-tiny").config, dtype=jnp.float32)
    params = init_transformer(jax.random.PRNGKey(0), cfg)
    tokens = jax.random.randint(jax.random.PRNGKey(1), (2, 16), 0, cfg.vocab_size)
    expected = transformer_forward(params, tokens, cfg)

    mesh = make_mesh({"dp": 1, "tp": 2})
    specs = transformer_param_specs(cfg)
    sharded = shard_pytree(params, specs, mesh)
    got = transformer_forward(sharded, tokens, cfg)
    np.testing.assert_allclose(
        np.asarray(expected), np.asarray(got), rtol=1e-4, atol=1e-4
    )


def test_train_step_dense_dp_tp():
    cfg = get_model("llama-tiny").config
    mesh = make_mesh({"dp": 2, "tp": 2})
    init_state, train_step, _ = make_train_step(cfg, mesh, sp=True)
    params, opt_state = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 32), 0, cfg.vocab_size)
    loss0, params, opt_state = train_step(params, opt_state, tokens)
    loss1, params, opt_state = train_step(params, opt_state, tokens)
    assert np.isfinite(float(loss0)) and np.isfinite(float(loss1))
    assert float(loss1) < float(loss0)  # same batch twice → loss must drop


def test_train_step_moe_ep():
    cfg = get_model("moe-tiny").config
    mesh = make_mesh({"dp": 2, "tp": 4})
    init_state, train_step, _ = make_train_step(cfg, mesh, sp=True, remat=True)
    params, opt_state = init_state(jax.random.PRNGKey(0))
    tokens = jax.random.randint(jax.random.PRNGKey(1), (4, 16), 0, cfg.vocab_size)
    loss, params, opt_state = train_step(params, opt_state, tokens)
    assert np.isfinite(float(loss))
    # Expert weights really are sharded over tp.
    w_gate = params["layers"]["w_gate"]
    spec = w_gate.sharding.spec
    assert spec[1] == "tp"


def test_graft_entry_contract():
    import __graft_entry__ as g

    fn, args = g.entry()
    out = jax.jit(fn)(*args)
    assert out.ndim == 3
    g.dryrun_virtual_mesh(8)


def test_dcn_init_noop_without_config():
    from gofr_tpu.config import MockConfig
    from gofr_tpu.parallel import initialize_multihost, process_topology

    assert initialize_multihost(MockConfig({})) is False
    topo = process_topology()
    assert topo["process_count"] == 1
    assert topo["global_devices"] == 8  # the virtual CPU mesh
