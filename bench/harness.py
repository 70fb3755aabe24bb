"""One run of one cell: set-up, warm-up, the measured window, the
metrics and the correctness check.

Everything a cell needs is found by name from ``BENCHMARK.json``:
``bench/configs/<config>.json`` (model keys as served, engine settings),
``bench/traffic/<traffic>.json`` (the mix, read by
:mod:`bench.traffic.gen`), ``bench/metrics/<metric>.py`` (one reader
per metric) and ``bench/limits/<cell>.json`` (the correctness limit).
Adding a cell, a mix or a metric adds files and entries; no code here
names one.

The window drives the engine's public served path: ``Engine.submit``
between steps once a request is due, then ``Engine.step``. Every token
event is stamped on the host clock when ``step()`` returns; the step
samples on the host, so the device has finished by then.
"""
from __future__ import annotations

import dataclasses
import importlib.util
import json
import math
import os
import shutil
import time

import numpy as np

from bench import stats
from bench.traffic import gen

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "bench")
DRAIN_S = 60.0          # longest wait past the window for a first token


class NoChip(SystemExit):
    """No accelerator, or one the peaks table does not know."""


class Stalled(SystemExit):
    """An end-to-end metric read no finite number."""


# -- the cell's files ---------------------------------------------------------


def _json(path: str) -> dict:
    with open(path) as f:
        return json.load(f)


@dataclasses.dataclass
class Cell:
    name: str
    config: dict
    mix: dict
    metrics: list          # metric entries the run reports, trace 0 or 1
    limits: dict
    chips: int = 1


def load_cell(name: str, trace: bool, root: str = ROOT) -> Cell:
    bm = _json(os.path.join(root, "BENCHMARK.json"))
    (w,) = [w for w in bm["workloads"] if w["name"] == name] or [None]
    if w is None:
        raise SystemExit(f"no workload {name!r} in BENCHMARK.json")
    (c,) = [c for c in bm["configs"] if c["name"] == w["config"]]
    kind = "per_layer" if trace else "end_to_end"
    metrics = [m for m in bm[kind]
               if name in m.get("workloads", [name])]
    return Cell(name=name, config=_json(os.path.join(root, c["file"])),
                mix=gen.load(os.path.join(root, "bench", "traffic",
                                          w["traffic"] + ".json")),
                metrics=metrics,
                limits=_json(os.path.join(root, "bench", "limits",
                                          name + ".json")),
                chips=w["chips"])


def reader(metric: str, root: str = ROOT):
    """The ``read(record, trace)`` function of ``bench/metrics/<metric>.py``."""
    path = os.path.join(root, "bench", "metrics", metric + ".py")
    spec = importlib.util.spec_from_file_location(
        "bench_metric_" + metric.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# -- device and program -------------------------------------------------------


def chip(dry: bool, chips: int = 1):
    """The device the run measures, and its row of ``bench/peaks.json``.
    A measured run needs ``chips`` TPUs of a kind the table holds; a dry
    run needs the CPU, where the kernels run in interpret mode."""
    import jax
    peaks = _json(os.path.join(BENCH, "peaks.json"))["devices"]
    dev = jax.devices()[0]
    if dry:
        if dev.platform != "cpu":
            raise NoChip("--dry-run is for the CPU")
        return dev, None
    if dev.platform != "tpu":
        raise NoChip(f"needs a TPU; JAX found {dev.platform!r} "
                     f"({dev.device_kind})")
    if dev.device_kind not in peaks:
        raise NoChip(f"device_kind {dev.device_kind!r} is not in "
                     f"bench/peaks.json")
    if len(jax.devices()) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX found "
                     f"{len(jax.devices())}")
    return dev, peaks[dev.device_kind]


def compile_cache() -> str:
    """``JAX_COMPILATION_CACHE_DIR`` if set, else ``<checkout>/.jax_cache``:
    a fixed path, since the path is part of the cache's key."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or os.path.join(
        ROOT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path


REPO_KEYS = {      # published key -> the program's ModelConfig field
    "hidden_size": "d_model", "num_hidden_layers": "num_layers",
    "num_attention_heads": "num_heads", "num_key_value_heads":
    "num_kv_heads", "head_dim": "head_dim", "intermediate_size": "d_ff",
    "vocab_size": "vocab_size", "tie_word_embeddings": "tie_embeddings",
    "rope_theta": "rope_theta", "qkv_bias": "qkv_bias",
}


def program_config(config: dict):
    """The program's model config, checked against the published keys
    the benchmark states it serves."""
    from repro import configs
    cfg = configs.get(config["repo_config"])
    if config.get("smoke"):
        cfg = configs.smoke(cfg)
    model = dict(config["model"])
    model.setdefault("head_dim", model["hidden_size"]
                     // model["num_attention_heads"])
    bad = {k: (model.get(k, False), getattr(cfg, f))
           for k, f in REPO_KEYS.items()
           if model.get(k, False) != getattr(cfg, f)}
    if bad or cfg.activation != "swiglu" or cfg.norm != "rmsnorm":
        raise SystemExit(f"program config {cfg.name} is not the model "
                         f"the benchmark states: {bad}")
    return cfg


def plan_for(cfg, spec, page_size: int):
    """The plan the program's own entry would serve: the committed
    ``plans/<arch>-<hw>.json`` if there is one, else tuned now for the
    Pallas backend."""
    from repro.core import plan as plan_mod
    path = plan_mod.default_plan_path(cfg, spec, os.path.join(ROOT, "plans"))
    if os.path.exists(path):
        return plan_mod.ExecutionPlan.load(path, cfg=cfg, spec=spec), path
    return plan_mod.tune(cfg, spec, backend="pallas",
                         page_size=page_size), "tuned at set-up"


def build(cell: Cell, seed: int, device, control: str | None):
    """(engine, program config, plan description) with weights drawn on
    the device from the seed."""
    import jax
    from repro import hardware
    from repro.models.api import get_model
    from repro.serving.engine import Engine
    from bench import weights

    cfg = program_config(cell.config)
    e = cell.config["engine"]
    spec = (hardware.TPU_V5E if device.platform == "cpu"
            else hardware.for_device(device))
    plan, where = plan_for(cfg, spec, e["page_size"])
    shapes = jax.eval_shape(get_model(cfg).init_params,
                            jax.random.PRNGKey(0))
    params = jax.block_until_ready(
        weights.for_program(cell.config["model"], seed, shapes))
    lower = ({"weight_dtype": "int8", "kv_dtype": "int8"}
             if control == "int8" else {})
    eng = Engine(cfg, params, num_slots=e["slots"], max_seq=e["max_seq"],
                 cache_kind="paged", page_size=e["page_size"],
                 num_pages=e["num_pages"], prefill_chunk=e["prefill_chunk"],
                 plan=plan, prefix_sharing=e["prefix_sharing"], seed=seed,
                 **lower)
    return eng, cfg, f"{eng.plan.describe()} ({where})"


# -- warm-up -----------------------------------------------------------------


def _serve_all(eng, prompts, max_new: int) -> None:
    from repro.serving.request import SamplingParams
    sp = SamplingParams(max_new_tokens=max_new)
    rids = [eng.submit(p, sp) for p in prompts]
    while not all(eng.requests[r].finished for r in rids):
        eng.step()


def _pow2_upto(n: int) -> list:
    out, b = [], 1
    while b < 2 * n:
        out.append(b)
        b *= 2
    return out


def warm(eng, cell: Cell, seed: int) -> int:
    """Compile every program shape this cell's traffic can reach, through
    the public API; returns the number of warm-up requests.

    * One prompt of the mix's longest length: its chunks walk the
      prefill's page-table widths from one page up to the whole table,
      and it decodes.
    * With shared prefixes: one anchor request per warm-up prefix (of
      the mix's prefix length) stays resident, so each later wave maps
      the prefix instead of prefilling it; then one short wave per
      reachable shape of the grouped decode's operands (number of
      groups and size of the largest, each rounded up to a power of
      two) joins the anchors for one grouped decode tick. The anchors
      are aborted at the end.
    """
    from repro.serving.request import SamplingParams
    rng = np.random.default_rng([seed, 0x3A93])
    vocab = eng.cfg.vocab_size
    longest, _ = gen.longest(cell.mix)
    _serve_all(eng, [rng.integers(1, vocab, longest, dtype=np.int32)], 2)
    n = 1
    sp = cell.mix.get("shared_prefix")
    if not (sp and cell.config["engine"]["prefix_sharing"]):
        return n
    slots = cell.config["engine"]["slots"]
    prefixes = [rng.integers(1, vocab, sp["tokens"], dtype=np.int32)
                for _ in range(sp["count"])]

    def own(p):
        return np.concatenate([p, rng.integers(1, vocab, 8, dtype=np.int32)])

    anchors = [eng.submit(own(p), SamplingParams(
        max_new_tokens=eng.max_seq - sp["tokens"] - 8)) for p in prefixes]
    while any(not eng.requests[r].tokens for r in anchors):
        eng.step()
    for ng in _pow2_upto(sp["count"]):
        groups = 1 if ng == 1 else ng // 2 + 1
        for m in _pow2_upto(slots)[1:]:
            big = 2 if m == 2 else m // 2 + 1
            if (groups > sp["count"]
                    or len(anchors) + big - 1 + groups - 1 > slots):
                continue
            prompts = ([own(prefixes[0]) for _ in range(big - 1)]
                       + [own(prefixes[g]) for g in range(1, groups)])
            _serve_all(eng, prompts, 2)
            n += len(prompts)
    for r in anchors:
        eng.abort(r)
    return n + len(anchors)


# -- the measured window ------------------------------------------------------


class _Compiles:
    """Counts programs lowered or compiled while armed."""

    EVENTS = ("/jax/core/compile/jaxpr_to_mlir_module_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self):
        import jax
        self.n = 0
        self.armed = False
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, event, duration, **_):
        if self.armed and event in self.EVENTS:
            self.n += 1


def drive(eng, reqs: list, mix: dict, seconds: float, trace_dir=None,
          clock=time.perf_counter, drain_s: float = DRAIN_S):
    """Serve ``reqs`` under the mix's loop; return (logs, steps, window,
    counters, compiles in the window).

    Open loop: a request is submitted between steps once its due time
    has passed, and timed from that due time. Closed loop: ``clients``
    requests start together and each finished request sends the client's
    next one. The window opens ``preroll_s`` after the load starts and
    closes ``seconds`` later; then the run goes on, load included, until
    every request due in the window has its first token, or for at most
    ``drain_s`` seconds.
    """
    import jax
    from repro.serving.request import SamplingParams
    ann = jax.profiler.TraceAnnotation
    open_loop = mix["loop"] == "open"
    logs = [stats.ReqLog(due=math.nan, prompt_len=len(r.prompt),
                         prefix_id=r.prefix_id, prefix_len=r.prefix_len,
                         max_new=r.max_new) for r in reqs]
    by_rid, steps = {}, []
    compiles = _Compiles()
    counters = {}
    start = clock()
    open_t = start + mix["preroll_s"]
    close_t = open_t + seconds
    win = [math.nan, math.nan]
    window_ann = None
    tracing = False
    nxt = 0
    inflight = 0

    def submit(i, due):
        nonlocal inflight
        logs[i].due = due
        with ann("bench.submit"):
            try:
                rid = eng.submit(reqs[i].prompt, SamplingParams(
                    max_new_tokens=reqs[i].max_new))
            except ValueError:
                logs[i].failed = True
                return
        logs[i].submit = clock()
        by_rid[rid] = i
        inflight += 1

    if not open_loop:
        for nxt in range(mix["clients"]):
            submit(nxt, start)
        nxt += 1
    saved = eng.stats.saved_prefill_tokens
    while True:
        now = clock()
        if trace_dir and not tracing and now >= open_t - 1.0:
            opts = jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0
            jax.profiler.start_trace(trace_dir, profiler_options=opts)
            tracing = True
            now = clock()
        if math.isnan(win[0]) and now >= open_t:
            win[0] = now
            counters["open"] = dataclasses.asdict(eng.stats)
            compiles.armed = True
            window_ann = ann("bench.window")
            window_ann.__enter__()
        if math.isnan(win[1]) and now >= close_t:
            win[1] = now
            counters["close"] = dataclasses.asdict(eng.stats)
            compiles.armed = False
            window_ann.__exit__(None, None, None)
            if tracing:
                jax.profiler.stop_trace()
        if not math.isnan(win[1]):
            due = [r for r in logs if win[0] <= r.due < win[1]]
            if (all(r.stamps or r.failed for r in due)
                    or now > win[1] + drain_s):
                break
        if open_loop:
            while nxt < len(reqs) and start + reqs[nxt].due_s <= now:
                submit(nxt, start + reqs[nxt].due_s)
                nxt += 1
        if inflight == 0:
            if open_loop and nxt < len(reqs):
                with ann("bench.wait_due"):
                    time.sleep(max(0.0, min(start + reqs[nxt].due_s,
                                            open_t if math.isnan(win[0])
                                            else close_t) - clock()))
                continue
            if not open_loop and nxt >= len(reqs):
                raise RuntimeError("closed loop ran out of requests")
        t0 = clock()
        with ann("engine.step"):
            events = eng.step()
        t1 = clock()
        decode, prefilled = [], []
        for ev in events:
            i = by_rid[ev.rid]
            r = logs[i]
            if ev.token is not None:
                r.stamps.append(t1)
                r.tokens.append(ev.token)
                if ev.index == 0:
                    prefilled.append(i)
                else:
                    decode.append((i, r.prompt_len + ev.index))
            if ev.finished:
                r.finished = True
                inflight -= 1
                if not open_loop and nxt < len(reqs):
                    submit(nxt, t1)
                    nxt += 1
        now_saved = eng.stats.saved_prefill_tokens
        steps.append(stats.StepLog(t0, t1, decode, prefilled,
                                   now_saved - saved))
        saved = now_saved
    return logs, steps, tuple(win), counters, compiles.n


# -- one run -----------------------------------------------------------------


def run(cell: Cell, seed: int, seconds: float, trace: bool, t_start: float,
        *, dry: bool = False, control: str | None = None,
        out_dir: str | None = None, log=print) -> dict:
    """Set up, warm up, measure, check; return the result object."""
    import gc
    import jax
    from bench import correct, trace as trace_mod, weights

    device, peaks = chip(dry, cell.chips)
    cache_dir = "off" if dry else compile_cache()
    log(f"device {device.platform} {device.device_kind!r} "
        f"x{len(jax.devices())}; compile cache {cache_dir}")
    eng, cfg, plan_desc = build(cell, seed, device, control)
    log(f"model {cfg.name} ({cell.config['source']}); plan {plan_desc}"
        + (f"; CONTROL {control}" if control else ""))
    t = time.perf_counter()
    n_warm = warm(eng, cell, seed)
    log(f"warm-up: {n_warm} requests in {time.perf_counter() - t:.3f} s")
    model = cell.config["model"]
    horizon = cell.mix["preroll_s"] + seconds + DRAIN_S
    reqs = gen.generate(cell.mix, seed, horizon, cfg.vocab_size,
                        n_min=512,
                        first_block=gen.first_block(cell.mix, seconds))
    out_dir = out_dir or os.path.join(BENCH, "out")
    trace_dir = None
    if trace:
        trace_dir = os.path.join(out_dir, f"trace-{cell.name}-{seed}")
        shutil.rmtree(trace_dir, ignore_errors=True)
    logs, steps, win, counters, n_comp = drive(
        eng, reqs, cell.mix, seconds, trace_dir)
    setup_s = win[0] - t_start
    rec = stats.Record(slots=cell.config["engine"]["slots"], open=win[0],
                       close=win[1], requests=logs, steps=steps,
                       counters=counters, setup_s=setup_s,
                       dims=weights.dims(model), peaks=peaks or {},
                       compiles_in_window=n_comp)
    tt = stats.ttfts(rec)
    log(f"window {rec.seconds:.3f} s: {len(tt)} requests due, "
        f"{sum(math.isfinite(x) for x in tt)} with a first token, "
        f"{len(stats.gaps(rec))} token gaps, "
        f"{stats.tokens_in_window(rec)} tokens, "
        f"{len(rec.window_steps())} steps; ttft p50 "
        f"{stats.percentile(tt, 50) * 1e3:.1f} ms; compiles in window "
        f"{n_comp}")
    summary = None
    if trace:
        xs = trace_mod.find_xspace(trace_dir)
        e = cell.config["engine"]
        summary = trace_mod.reduce(
            xs, os.path.join(BENCH, "trace_names.json"),
            trace_mod.widths(weights.dims(model), e["slots"],
                             e["page_size"], e["prefill_chunk"])
        ) if xs else None
        with open(os.path.join(out_dir, f"trace-{cell.name}-{seed}.json"),
                  "w") as f:
            json.dump(summary, f, indent=1)
        shutil.rmtree(trace_dir, ignore_errors=True)
    metrics = {}
    for m in cell.metrics:
        v = reader(m["name"])(rec, summary)
        if (v is None or math.isnan(v)) and trace:
            continue            # a per-layer reader found nothing to read
        if v is None or not math.isfinite(v):
            # an end-to-end metric always has something to read, and an
            # infinite reading (a tail of requests that never got a
            # token) is a stall: the run is refused, not shortened
            raise Stalled(f"{m['name']} read {v!r}: {len(tt)} requests "
                          f"due, {sum(math.isinf(x) for x in tt)} with no "
                          f"first token {DRAIN_S:.0f} s past the window")
        metrics[m["name"]] = {"value": v, "unit": m["unit"]}
    mem = (device.memory_stats() or {}).get("peak_bytes_in_use", 0)
    attempted = sum(1 for r in logs if win[0] <= r.due < win[1])
    failed = sum(1 for r in logs if win[0] <= r.due < win[1]
                 and (r.failed or not r.stamps))
    # free the program's state before the reference takes the device
    prompts = {i: r.prompt for i, r in enumerate(reqs)}
    del eng, reqs
    gc.collect()
    t = time.perf_counter()
    ok, checks = correct.judge(model, seed, logs, prompts, cell.limits,
                               cell.config["engine"]["max_seq"],
                               control=control == "ref-int8", log=log)
    log(f"reference check in {time.perf_counter() - t:.3f} s")
    result = {"correct": ok, "attempted": attempted, "failed": failed,
              "metrics": metrics,
              "device": {"platform": device.platform,
                         "kind": device.device_kind,
                         "count": len(jax.devices()),
                         "memory_peak_bytes": int(mem)}}
    if summary:
        result["device"]["busy_s"] = summary["busy_s"]
        result["device"]["window_s"] = summary["window_s"]
        result["breakdown"] = {"device_ops": summary["device_ops"],
                               "idle_gaps": summary["idle_gaps"]}
    result["checks"] = checks
    return result


def dry_cell(trace: bool, root: str = ROOT) -> Cell:
    """The CPU rehearsal's cell: the smoke configuration, mix and limits
    in ``bench/tests/data``, with every metric of ``BENCHMARK.json`` of
    the kind the run reports."""
    data = os.path.join(root, "bench", "tests", "data")
    bm = _json(os.path.join(root, "BENCHMARK.json"))
    return Cell(name="smoke", config=_json(os.path.join(data,
                                                        "smoke-config.json")),
                mix=gen.load(os.path.join(data, "smoke-traffic.json")),
                metrics=bm["per_layer" if trace else "end_to_end"],
                limits=_json(os.path.join(data, "smoke-limits.json")))
