"""Ahead-of-time compiles of phi3-mini-3.8b's served steps for a TPU v5e.

The phi3 configuration (``bench/configs/phi3-mini-3.8b.json``: 32
layers, d_model 3072, 32/32 heads, head_dim 96, d_ff 8192, vocab 32064,
untied head), kept for a long-decode cell once the program's RMSNorm
takes Phi-3's epsilon, is served through the engine's paged decode and
chunked-prefill steps under a Pallas plan tuned for it. These tests
lower both steps at the configuration's engine shapes
(8 slots, page 64, 32 pages per sequence, chunk 64) for one chip of a
described ``v5e:2x2`` topology and compile them with the TPU compiler,
which refuses tilings, VMEM budgets and memory the chip cannot hold.
They check that the Mosaic kernels are in the program
(``tpu_custom_call``) and that the program fits the chip. Nothing runs.

The topology is described inside a module fixture, never at import.
"""
import json
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

HERE = os.path.dirname(os.path.abspath(__file__))
CONFIG = os.path.join(HERE, "..", "configs", "phi3-mini-3.8b.json")


@pytest.fixture(scope="module")
def one_chip():
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache as cc
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - any failure means "cannot describe"
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    # entries compiled for a described chip cannot be read back without one
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    cc.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    cc.reset_cache()


@pytest.fixture(scope="module")
def served(one_chip):
    """(api, ctx, shapes) of the cell's engine, as ShapeDtypeStructs on
    the described chip, with the kernels targeting the chip (not
    interpret mode)."""
    from repro import configs, hardware
    from repro.core import plan as plan_mod
    from repro.kernels import ops
    from repro.models.api import get_model
    from repro.models.kvlayout import PagedLayout
    from repro.models.layers import LayerCtx

    with open(CONFIG) as f:
        eng = json.load(f)["engine"]
    cfg = configs.get("phi3-mini-3.8b")
    plan = plan_mod.tune(cfg, hardware.TPU_V5E, backend="pallas",
                         page_size=eng["page_size"])
    api = get_model(cfg)
    place = lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype,  # noqa: E731
                                           sharding=one_chip)
    shapes = {
        "params": jax.tree.map(place, jax.eval_shape(
            api.init_params, jax.random.PRNGKey(0))),
        "cache": jax.tree.map(place, api.cache_spec(PagedLayout(
            eng["num_pages"], eng["page_size"], "bf16"))),
        "rows": place(jax.ShapeDtypeStruct((eng["slots"],), jnp.int32)),
        "tables": place(jax.ShapeDtypeStruct(
            (eng["slots"], eng["max_seq"] // eng["page_size"]), jnp.int32)),
        "chunk": place(jax.ShapeDtypeStruct(
            (eng["slots"], eng["prefill_chunk"]), jnp.int32)),
    }
    real = ops.kernel_target
    ops.kernel_target = lambda: (False, hardware.TPU_V5E)
    try:
        yield api, LayerCtx(cfg=cfg, plan=plan), shapes
    finally:
        ops.kernel_target = real


def _check(compiled):
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    used = (mem.argument_size_in_bytes + mem.output_size_in_bytes
            + mem.temp_size_in_bytes - mem.alias_size_in_bytes)
    assert used < 16e9, f"{used} bytes do not fit a 16 GB chip"


def test_phi3_decode_step_compiles(served):
    api, ctx, s = served
    step = jax.jit(lambda p, t, c, bt, le, po: api.decode_step(
        ctx, p, t, c, le, block_tables=bt, positions=po),
        donate_argnums=(2,))
    _check(step.lower(s["params"], s["rows"], s["cache"], s["tables"],
                      s["rows"], s["rows"]).compile())


def test_phi3_prefill_chunk_compiles(served):
    api, ctx, s = served
    step = jax.jit(lambda p, t, cl, c, bt, le: api.prefill_chunk(
        ctx, p, t, cl, c, le, block_tables=bt), donate_argnums=(3,))
    _check(step.lower(s["params"], s["chunk"], s["rows"], s["cache"],
                      s["tables"], s["rows"]).compile())
