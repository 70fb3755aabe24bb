"""Record the small TPU trace that ``test_trace.py`` reduces.

    python3 bench/tests/data/record_trace.py   # on a v5e

Inside a ``bench.window`` span it runs, as ``engine.step`` spans, the
program's paged decode-attention kernel over a 16-page pool (qwen2-0.5b
widths: 2 kv heads of 64, 7 query heads each, page 64, 2 rows) and a
(8, 896) x (896, 4864) product, each step followed by a 50 ms
``bench.wait_due`` sleep in which the device idles. The trace goes to
``bench/tests/data/trace.xplane.pb``.
"""
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

STEPS, SLEEP_S = 5, 0.05


def main() -> int:
    import jax
    import jax.numpy as jnp
    from repro.kernels.decode_attention import \
        paged_decode_attention_unified_max

    ann = jax.profiler.TraceAnnotation
    k = jax.random.split(jax.random.PRNGKey(0), 5)
    q = jax.random.normal(k[0], (2, 14, 64), jnp.bfloat16)
    kp = jax.random.normal(k[1], (16, 64, 2, 64), jnp.bfloat16)
    vp = jax.random.normal(k[2], (16, 64, 2, 64), jnp.bfloat16)
    bt = jnp.arange(16, dtype=jnp.int32).reshape(2, 8)
    lens = jnp.array([300, 500], jnp.int32)
    x = jax.random.normal(k[3], (8, 896), jnp.bfloat16)
    w = jax.random.normal(k[4], (896, 4864), jnp.bfloat16)
    attn = jax.jit(paged_decode_attention_unified_max)
    gemm = jax.jit(lambda a, b: jnp.dot(a, b))
    jax.block_until_ready((attn(q, kp, vp, bt, lens), gemm(x, w)))
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with ann("bench.window"):
        for _ in range(STEPS):
            with ann("engine.step"):
                jax.block_until_ready((attn(q, kp, vp, bt, lens),
                                       gemm(x, w)))
            with ann("bench.wait_due"):
                time.sleep(SLEEP_S)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    shutil.copy(path, os.path.join(HERE, "trace.xplane.pb"))
    shutil.rmtree(tmp)
    print(os.path.getsize(os.path.join(HERE, "trace.xplane.pb")), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
