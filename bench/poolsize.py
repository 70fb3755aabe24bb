"""Size a configuration's KV page pool as a deployment would.

    python3 bench/poolsize.py <config name> <bytes_limit>

The pool is the largest number of pages for which each compiled step
program the cell runs (paged decode, grouped decode where prefix sharing
is on, chunked prefill over the whole page table) fits in 0.9 of the
chip's ``memory_stats()["bytes_limit"]`` (vLLM's default share of device
memory), counting arguments, outputs not aliased to arguments, and
temporaries as ``compiled.memory_analysis()`` reports them. The programs
are compiled for one chip of a described ``v5e:2x2`` topology, so this
runs without the chip; it prints the count, which goes into the
configuration's ``engine.num_pages`` with how it was found.
"""
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]
SHARE = 0.9


def programs(config: dict, num_pages: int, sharding):
    """[(name, compiled)] of the cell's step programs at ``num_pages``."""
    import jax
    import jax.numpy as jnp
    from repro import hardware
    from repro.kernels.group_attention import DecodeGroups
    from repro.models.api import get_model
    from repro.models.kvlayout import PagedLayout
    from repro.models.layers import LayerCtx
    from bench import harness

    cfg = harness.program_config(config)
    e = config["engine"]
    plan, _ = harness.plan_for(cfg, hardware.TPU_V5E, e["page_size"])
    api = get_model(cfg)
    ctx = LayerCtx(cfg=cfg, plan=plan)

    def place(s):
        return jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=sharding)

    def i32(*shape):
        return place(jax.ShapeDtypeStruct(shape, jnp.int32))

    b, nb = e["slots"], e["max_seq"] // e["page_size"]
    params = jax.tree.map(place, jax.eval_shape(api.init_params,
                                                jax.random.PRNGKey(0)))
    cache = jax.tree.map(place, api.cache_spec(
        PagedLayout(num_pages, e["page_size"], "bf16")))
    out = []
    dec = jax.jit(lambda p, t, c, bt, le, po: api.decode_step(
        ctx, p, t, c, le, block_tables=bt, positions=po), donate_argnums=(2,))
    out.append(("decode", dec.lower(params, i32(b), cache, i32(b, nb),
                                    i32(b), i32(b)).compile()))
    if e["prefix_sharing"]:
        ng, lp, m = 8, 32, b       # the largest operand shapes
        groups = DecodeGroups(i32(ng, lp), i32(ng), i32(ng), i32(ng),
                              i32(ng, m), i32(b), i32(b), i32(b))
        gdec = jax.jit(lambda p, t, c, bt, le, gr, po: api.decode_step(
            ctx, p, t, c, le, block_tables=bt, decode_groups=gr,
            positions=po), donate_argnums=(2,))
        out.append(("grouped decode", gdec.lower(
            params, i32(b), cache, i32(b, nb), i32(b), groups,
            i32(b)).compile()))
    chunk = jax.jit(lambda p, t, cl, c, bt, le: api.prefill_chunk(
        ctx, p, t, cl, c, le, block_tables=bt), donate_argnums=(3,))
    out.append(("prefill chunk", chunk.lower(
        params, i32(b, e["prefill_chunk"]), i32(b), cache, i32(b, nb),
        i32(b)).compile()))
    return out


def needed(compiled) -> int:
    m = compiled.memory_analysis()
    return (m.argument_size_in_bytes + m.temp_size_in_bytes
            + m.output_size_in_bytes - m.alias_size_in_bytes)


def fits(config, num_pages, sharding, limit) -> tuple:
    try:
        progs = programs(config, num_pages, sharding)
    except Exception as e:  # noqa: BLE001 - the compiler refusing = no fit
        if "RESOURCE_EXHAUSTED" in str(e):
            return False, {"refused": str(e).splitlines()[0][:160]}
        raise
    use = {n: needed(c) for n, c in progs}
    return max(use.values()) <= limit, use


def main() -> int:
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    name, bytes_limit = sys.argv[1], int(float(sys.argv[2]))
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    from repro import hardware
    from repro.kernels import ops
    jax.config.update("jax_enable_compilation_cache", False)
    ops.kernel_target = lambda: (False, hardware.TPU_V5E)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    one = SingleDeviceSharding(topo.devices[0])
    with open(os.path.join(ROOT, "bench", "configs", name + ".json")) as f:
        config = json.load(f)
    e = config["engine"]
    limit = SHARE * bytes_limit
    lo = e["slots"]                          # one page per slot must fit
    hi = e["slots"] * (e["max_seq"] // e["page_size"]) * 64
    ok, use = fits(config, lo, one, limit)
    assert ok, f"{lo} pages do not fit: {use}"
    while hi - lo > max(1, lo // 64):        # to within 1.6 %
        mid = (lo + hi) // 2
        ok, use = fits(config, mid, one, limit)
        print(f"{mid} pages: {'fits' if ok else 'no'} {use}", flush=True)
        lo, hi = (mid, hi) if ok else (lo, mid)
    print(json.dumps({"config": name, "num_pages": lo,
                      "limit_bytes": limit,
                      "programs": fits(config, lo, one, limit)[1]}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
