"""Record the small TPU trace with the engine's own spans that
``test_spans.py`` reduces.

    python3 bench/tests/data/record_spans.py   # on a v5e

A paged, prefix-sharing engine at qwen2-0.5b's widths, cut to 2 layers
(2 slots, page 64, chunk 64, the Pallas backend), serves three requests
that share a 128-token header, the third waiting behind the full batch.
Inside a ``bench.window`` span every ``Engine.step`` runs inside an
``engine.step`` span, as the benchmark opens them, so the engine's
``engine.admit`` / ``engine.prefill`` / ``engine.grow`` /
``engine.decode`` / ``engine.sample`` spans nest inside; each step is
followed by a 20 ms ``bench.wait_due`` sleep. The same requests are
served once untraced first, so nothing compiles in the trace. The trace
goes to ``bench/tests/data/trace-spans.xplane.pb``.
"""
import dataclasses
import glob
import os
import shutil
import sys
import tempfile
import time

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(os.path.dirname(os.path.dirname(HERE)))
sys.path[:0] = [ROOT, os.path.join(ROOT, "src")]

LAYERS, SLEEP_S = 2, 0.02


def main() -> int:
    import jax
    import numpy as np
    from repro import configs, hardware
    from repro.core import plan as plan_mod
    from repro.models.api import get_model
    from repro.serving.engine import Engine
    from repro.serving.request import SamplingParams

    cfg = dataclasses.replace(configs.get("qwen2-0.5b"), num_layers=LAYERS)
    plan = plan_mod.tune(cfg, hardware.TPU_V5E, backend="pallas",
                         page_size=64)
    params = get_model(cfg).init_params(jax.random.PRNGKey(0))
    eng = Engine(cfg, params, num_slots=2, max_seq=512, cache_kind="paged",
                 page_size=64, prefill_chunk=64, plan=plan,
                 prefix_sharing=True)
    rng = np.random.default_rng(0)
    head = rng.integers(1, cfg.vocab_size, 128).astype(np.int32)
    prompts = [np.concatenate([head, rng.integers(1, cfg.vocab_size, n)
                               .astype(np.int32)]) for n in (20, 37, 29)]
    ann = jax.profiler.TraceAnnotation

    def serve(traced: bool) -> None:
        rids = [eng.submit(p, SamplingParams(max_new_tokens=3))
                for p in prompts]
        while not all(eng.requests[r].finished for r in rids):
            with ann("engine.step"):
                eng.step()
            if traced:
                with ann("bench.wait_due"):
                    time.sleep(SLEEP_S)

    serve(False)
    tmp = tempfile.mkdtemp()
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with ann("bench.window"):
        serve(True)
    jax.profiler.stop_trace()
    (path,) = glob.glob(os.path.join(tmp, "**", "*.xplane.pb"),
                        recursive=True)
    out = os.path.join(HERE, "trace-spans.xplane.pb")
    shutil.copy(path, out)
    shutil.rmtree(tmp)
    print(os.path.getsize(out), "bytes")
    return 0


if __name__ == "__main__":
    sys.exit(main())
