"""One run of one cell: load, warm up, measure, check, report.

Everything that belongs to one configuration, traffic mix or per-layer
metric is a file found by name: ``configs/<config>.json``,
``traffic/<mix>.json`` and ``metrics/<metric>.py`` (a ``read(ctx)`` that
returns the number, or None where the run gave it nothing to read). No code
here branches on a cell's name.
"""
from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import pathlib
import re
import shutil
import sys
import tempfile
import time
import types

import numpy as np

from chipbench import devtrace, traffic
from chipbench.engine import Client, SpanEngine, now_ns
from chipbench.reference import Reference, served_gaps
from chipbench.weights import layout, make_weights
from chipbench.work import Shapes

BENCH_DIR = pathlib.Path(__file__).resolve().parents[1]
REPO = BENCH_DIR.parents[1]
CACHE_DIR = BENCH_DIR / ".jax_cache"
TRACE_SECONDS = 8.0       # the profiler records this much of the window
DRAIN_SECONDS = 60.0      # a request due in the window may finish this late
WARMUP_TIMEOUT_S = 900.0  # the first run of a checkout compiles here


class NoAccelerator(RuntimeError):
    pass


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    cfg: dict
    mix: dict
    end_to_end: list
    per_layer: list


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, spec_path: pathlib.Path | None = None,
              bench_dir: pathlib.Path = BENCH_DIR) -> Cell:
    spec = json.loads((spec_path or REPO / "BENCHMARK.json").read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise KeyError(f"no workload {name!r} in BENCHMARK.json")
    w = cells[name]
    cfg = json.loads((bench_dir / "configs" / f"{w['config']}.json")
                     .read_text())
    mix = json.loads((bench_dir / "traffic" / f"{w['traffic']}.json")
                     .read_text())
    return Cell(name, w["chips"], cfg, mix,
                [m for m in spec["end_to_end"] if _applies(m, name)],
                [m for m in spec["per_layer"] if _applies(m, name)])


def metric_reader(name: str, bench_dir: pathlib.Path = BENCH_DIR):
    path = bench_dir / "metrics" / f"{name}.py"
    mod_name = "chipbench_metric_" + re.sub(r"\W", "_", name)
    spec = importlib.util.spec_from_file_location(mod_name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


# ------------------------------------------------------------------ device
def devices_or_fail(chips: int):
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoAccelerator(f"needs a TPU, JAX found {devs[0].platform}")
    if len(devs) < chips:
        raise NoAccelerator(f"the cell needs {chips} chips, JAX found "
                            f"{len(devs)}")
    return devs


def use_compile_cache(path: pathlib.Path) -> None:
    """JAX's persistent cache at a fixed path in the checkout, for every
    program however short its compile (eager prefill ops included)."""
    import jax
    jax.config.update("jax_compilation_cache_dir", str(path))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


class CompileCounter:
    """Programs compiled or loaded from the persistent cache (one event per
    backend compile request), and the requests the cache missed, by
    monitoring listeners."""

    def __init__(self):
        import jax
        self.names: list = []
        self.cache_misses = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)
        jax.monitoring.register_event_listener(self._on_event)

    def _on_event(self, event: str, **_):
        if event == "/jax/compilation_cache/cache_misses":
            self.cache_misses += 1

    @property
    def count(self) -> int:
        return len(self.names)

    def _on(self, event: str, duration: float, fun_name: str = "?", **_):
        if event == "/jax/core/compile/backend_compile_duration":
            self.names.append(fun_name)


class GcClock:
    """Seconds the interpreter spent collecting garbage, by gc.callbacks:
    time in which no Python thread (the client's among them) ran."""

    def __init__(self):
        self.seconds = 0.0
        self._t0 = None
        gc.callbacks.append(self._on)

    def _on(self, phase: str, _info) -> None:
        if phase == "start":
            self._t0 = time.perf_counter()
        elif self._t0 is not None:
            self.seconds += time.perf_counter() - self._t0
            self._t0 = None


# configuration-file key -> the program's ModelConfig field
FIELDS = {"num_hidden_layers": "n_layers", "hidden_size": "d_model",
          "num_attention_heads": "n_heads",
          "num_key_value_heads": "n_kv_heads", "head_dim": "head_dim",
          "intermediate_size": "d_ff", "vocab_size": "vocab_size",
          "tie_word_embeddings": "tie_embeddings", "qk_norm": "qk_norm",
          "qkv_bias": "qkv_bias", "mlp_gated": "mlp_gated",
          "norm": "norm_type", "norm_eps": "rms_eps",
          "rope_theta": "rope_theta"}
# the MLPs the reference computes: the program's (mlp_gated, mlp_act) for
# each ``hidden_act``
ACTIVATIONS = {(True, "silu"): "silu", (False, "gelu"): "gelu_pytorch_tanh"}


def _program_tree(pc) -> dict:
    """{path: shape} of every leaf of the program's parameter tree."""
    from repro.models.params import ParamSpec, build_param_specs

    def flat(node, prefix):
        for k, v in node.items():
            if isinstance(v, ParamSpec):
                yield prefix + (k,), tuple(v.shape)
            else:
                yield from flat(v, prefix + (k,))
    return dict(flat(build_param_specs(pc), ()))


def _program_window(pc):
    """The attention window the program applies (0: none), as
    ``models/lm.py`` ``is_local_flags`` and ``attention._mask`` apply it."""
    w, period = pc.sliding_window, pc.local_global_period
    if not w or period in (0, 1):
        return w  # on every layer
    return f"{w} on {period - 1} of every {period} layers"


def program_architecture(pc) -> dict:
    """The architecture the program computes, under the configuration
    file's keys: the inverse of ``program_config``. ``out_bias`` is read
    from the parameter tree, which has no flag for it."""
    arch = {k: getattr(pc, f) for k, f in FIELDS.items()}
    arch.update(hidden_act=ACTIVATIONS.get((pc.mlp_gated, pc.mlp_act),
                                           f"{pc.mlp_act}, not computed by "
                                           "the reference"),
                out_bias=("layers", "attn", "bo") in _program_tree(pc),
                attention_window=_program_window(pc))
    return arch


def program_config(cfg: dict):
    """The program's configuration for this file, checked against the
    file's whole architecture before any weights are drawn: every key of
    ``program_architecture`` and the served dtype, and the program's
    parameter tree against the one ``weights.layout`` draws, path by path
    and shape by shape. A program that would ignore a leaf the file draws
    (a bias, a norm's shift) is refused here, where the logit-gap check
    could pass it within its limit."""
    from repro.configs import get_config
    s = cfg["serve"]
    pc = get_config(s["repro_arch"], smoke=s.get("repro_smoke", False))
    bad = [f"{k}: file {cfg[k]!r}, program {v!r}"
           for k, v in program_architecture(pc).items() if cfg[k] != v]
    if s["dtype"] != pc.dtype:
        bad.append(f"dtype: file {s['dtype']!r}, program {pc.dtype!r}")
    want = {p: shape for p, (shape, _) in layout(cfg, pc.vocab_padded).items()}
    have = _program_tree(pc)
    for p in sorted(want.keys() | have.keys()):
        if want.get(p) != have.get(p):
            bad.append(f"{'.'.join(p)}: file draws {want.get(p, 'none')}, "
                       f"program has {have.get(p, 'none')}")
    if bad:
        raise ValueError(f"program config {pc.name} departs from the "
                         "configuration file:\n  " + "\n  ".join(bad))
    return pc


# ------------------------------------------------------------------ serving
@dataclasses.dataclass
class Served:
    client: Client
    prefill_spans: list
    decode_spans: list
    window: tuple             # (start_ns, end_ns), host monotonic clock
    trace: dict | None        # devtrace.extract output
    trace_host: tuple         # host monotonic span the profiler recorded
    compiles_in_window: list  # names of the programs compiled or loaded
    cache_misses_in_window: int
    memory_peak_bytes: int | None
    gc_s_in_window: float = 0.0


def _warm_up(eng, lengths, vocab: int, seed: int) -> None:
    """One request per prompt length the schedule uses, through the same
    prefill, splice and decode the window drives."""
    rng = np.random.default_rng([seed, 3])
    reqs = [eng.submit(rng.integers(0, vocab, n, dtype=np.int32),
                       max_new_tokens=1) for n in sorted(set(lengths))]
    deadline = time.monotonic() + WARMUP_TIMEOUT_S
    for r in reqs:
        if not r.done_event.wait(max(0.0, deadline - time.monotonic())):
            raise TimeoutError("warm-up did not finish")
    if any(len(r.tokens) != 2 for r in reqs):
        raise RuntimeError("warm-up requests came back short")


def _sleep_until(t_ns: int) -> None:
    while True:
        left = (t_ns - now_ns()) / 1e9
        if left <= 0:
            return
        time.sleep(min(left, 0.5))


def serve(cell: Cell, seed: int, seconds: float, *, trace: bool,
          rate: float | None = None, engine_cls=SpanEngine,
          weights=None, on_setup_done=None) -> tuple:
    """Serve the cell's traffic: returns (Served, weights).
    The engine and its runtime are stopped and the cache freed on return."""
    import jax

    from repro.core import TaskRuntime
    cfg, mix = cell.cfg, cell.mix
    s = cfg["serve"]
    pc = program_config(cfg)
    if weights is None:
        weights = make_weights(cfg, seed, pc.vocab_padded)
    sched = traffic.schedule(mix, seconds, rate)
    prompts = traffic.prompt_tokens(seed, sched, cfg["vocab_size"])
    rt = TaskRuntime(n_workers=s["runtime_workers"]).start()
    eng = None
    try:
        eng = engine_cls(pc, weights, rt, n_slots=s["n_slots"],
                         max_seq=s["max_seq"]).start()
        _warm_up(eng, [a.prompt_len for a in sched], cfg["vocab_size"], seed)
        eng.clear_spans()
        # the garbage that tracing and compiling left is collected here, in
        # set-up, and not by a pause in the window
        gc.collect()
        counter, gcc = CompileCounter(), GcClock()
        if on_setup_done is not None:
            on_setup_done()
        start = now_ns() + 20_000_000
        w0 = start + int(mix["lead_in_s"] * 1e9)
        w1 = w0 + int(seconds * 1e9)
        client = Client(eng, sched, prompts).start(start)
        _sleep_until(w0)
        c0, m0, g0 = counter.count, counter.cache_misses, gcc.seconds
        ex, th = None, (w0, w0)
        if trace:
            ex, th = _traced(min(w1, w0 + int(TRACE_SECONDS * 1e9)))
        _sleep_until(w1)
        compiles = counter.names[c0:]
        misses = counter.cache_misses - m0
        gc_s = gcc.seconds - g0
        client.join(timeout=30.0)
        client.wait_done(w1 + int(DRAIN_SECONDS * 1e9))
        if all(_complete(x) for x in client.sent):
            eng.stop(drain=True, timeout=DRAIN_SECONDS)  # re-raises errors
        else:
            eng.stop(drain=False)  # requests still open now have failed
        peak = (jax.devices()[0].memory_stats() or {}).get("peak_bytes_in_use")
        out = Served(client, eng.prefill_spans, eng.decode_spans, (w0, w1),
                     ex, th, compiles, misses, peak, gc_s)
    finally:
        if eng is not None:
            eng.stop(drain=False)
            eng.cache = None
        rt.shutdown(wait=False)
    return out, weights


def _traced(until_ns: int) -> tuple:
    import jax
    opts = jax.profiler.ProfileOptions()
    opts.host_tracer_level = 1
    opts.python_tracer_level = 0
    log_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    try:
        t0 = now_ns()
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        _sleep_until(until_ns)
        jax.profiler.stop_trace()
        return devtrace.extract(devtrace.find_xplane(log_dir)), (t0, until_ns)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


# ------------------------------------------------------------------ metrics
def _in_window(served: Served) -> list:
    return [s for s in served.client.sent if s.arrival.in_window]


def _complete(s) -> bool:
    return (s.req is not None and not s.req.rejected
            and len(s.req.tokens) == s.arrival.output_len
            and len(s.token_ns) == s.arrival.output_len)


def latencies_ms(served: Served) -> tuple:
    """(time to first token from when each request was due, every gap
    between consecutive tokens), over the window's finished requests."""
    done = [s for s in _in_window(served) if _complete(s)]
    ttft = [(s.token_ns[0] - s.due_ns) / 1e6 for s in done]
    itl = [g / 1e6 for s in done for g in np.diff(s.token_ns)]
    return ttft, itl


def percentiles(xs, qs=(50, 90, 95, 99)) -> dict:
    return {f"p{q}": float(np.percentile(xs, q)) for q in qs} if len(xs) \
        else {}


def end_to_end(served: Served, seconds: float, setup_s: float) -> dict:
    ttft, itl = latencies_ms(served)
    w0, w1 = served.window
    toks = sum(w0 <= t <= w1 for s in served.client.sent for t in s.token_ns)
    out = {"setup_s": setup_s, "serve_tok_s": toks / seconds}
    if ttft:
        out["ttft_p95_ms"] = float(np.percentile(ttft, 95))
    if itl:
        out["itl_p50_ms"] = float(np.percentile(itl, 50))
        out["itl_p95_ms"] = float(np.percentile(itl, 95))
    return out


def per_layer(cell: Cell, served: Served, device: dict, peak: dict,
              bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    ctx = types.SimpleNamespace(
        window=served.window, prefill_spans=served.prefill_spans,
        decode_spans=served.decode_spans, trace=served.trace,
        trace_host=served.trace_host, device=device,
        shapes=Shapes.from_config(cell.cfg), peak=peak)
    out = {}
    for m in cell.per_layer:
        v = metric_reader(m["name"], bench_dir)(ctx)
        if v is not None:
            out[m["name"]] = float(v)
    return out


def peaks_for(kind: str, bench_dir: pathlib.Path = BENCH_DIR) -> dict:
    table = json.loads((bench_dir / "peaks.json").read_text())
    if kind not in table:
        raise KeyError(f"no peaks for device kind {kind!r} in peaks.json")
    return table[kind]


# ------------------------------------------------------------------ outputs
def sample_for_check(served: Served, seed: int, want: dict) -> list:
    """Finished requests of the window, drawn from the seed, the longest
    first, until they hold the mix's ``check_sample`` tokens."""
    done = [s for s in _in_window(served) if _complete(s)]
    if not done:
        return []
    longest = max(done, key=lambda s: s.arrival.prompt_len +
                  s.arrival.output_len)
    rest = [s for s in done if s is not longest]
    rng = np.random.default_rng([seed, 4])
    picked, n = [longest], longest.arrival.output_len
    for i in rng.permutation(len(rest)):
        if n >= want["tokens"] or len(picked) >= want["max_requests"]:
            break
        picked.append(rest[i])
        n += rest[i].arrival.output_len
    return picked


def teacher_forced(ref: Reference, weights, sent) -> tuple:
    """(sequence, rows, served tokens) the reference is run over: the prompt
    and every served token but the last; row i predicts served token i."""
    served = np.asarray(sent.req.tokens, np.int64)
    seq = np.concatenate([sent.prompt, served[:-1].astype(np.int32)])
    n = len(sent.prompt)
    rows = np.arange(n - 1, n - 1 + len(served))
    return seq, rows, served


def check_outputs(cfg: dict, weights, sample: list) -> float:
    """Widest gap, in logits, by which a served token lies below the
    float32 reference's best, over the sampled requests."""
    ref = Reference(cfg)
    worst = 0.0
    for s in sample:
        seq, rows, served = teacher_forced(ref, weights, s)
        worst = max(worst, float(served_gaps(
            ref.logits(weights, seq, rows), served).max()))
    return worst


def checks(cell: Cell, served: Served, weights, seed: int) -> dict:
    """Each number compared, with its limit. ``sampled_tokens_at_least``
    is a floor, the others are ceilings."""
    missing = sum(not _complete(s) for s in _in_window(served))
    want = cell.mix["check_sample"]
    sample = sample_for_check(served, seed, want)
    gap = check_outputs(cell.cfg, weights, sample) if sample else float("inf")
    return {"requests_incomplete": {"value": missing, "limit": 0},
            "sampled_tokens_at_least": {
                "value": sum(len(s.req.tokens) for s in sample),
                "limit": want["tokens"]},
            "max_logit_gap": {"value": gap,
                              "limit": cell.cfg["check"]["max_logit_gap"]}}


def is_correct(c: dict) -> bool:
    lim = c["max_logit_gap"]["limit"]
    return (c["requests_incomplete"]["value"] == 0
            and c["sampled_tokens_at_least"]["value"]
            >= c["sampled_tokens_at_least"]["limit"]
            and lim is not None and c["max_logit_gap"]["value"] <= lim)


def diag(**kw) -> None:
    print("bench-diag " + json.dumps(kw, default=str), flush=True)


def run(cell_name: str, seed: int, seconds: float, trace: bool, *,
        t_start: float, spec_path=None, bench_dir=BENCH_DIR,
        require_accelerator: bool = True, engine_cls=SpanEngine) -> dict:
    """A whole run: the result object the last line of output carries."""
    cell = load_cell(cell_name, spec_path, bench_dir)
    import jax
    devs = devices_or_fail(cell.chips) if require_accelerator \
        else jax.devices()
    if require_accelerator:
        use_compile_cache(CACHE_DIR)
    peak = peaks_for(devs[0].device_kind, bench_dir) if trace else None
    clock = {}
    served, weights = serve(
        cell, seed, seconds, trace=trace, engine_cls=engine_cls,
        on_setup_done=lambda: clock.setdefault("setup_s",
                                               time.monotonic() - t_start))
    lat = served.client.lateness_ms()
    diag(cell=cell.name, seed=seed,
         compiles_in_window=len(served.compiles_in_window),
         compiled_in_window=sorted(set(served.compiles_in_window)),
         cache_misses_in_window=served.cache_misses_in_window,
         gc_s_in_window=served.gc_s_in_window,
         generator_late_ms_p95=float(np.percentile(lat, 95)) if len(lat)
         else None, generator_late_ms_max=float(lat.max()) if len(lat)
         else None, requests=len(served.client.sent),
         prefills=len(served.prefill_spans),
         decode_iters=len(served.decode_spans))
    ttft, itl = latencies_ms(served)
    diag(ttft_ms=percentiles(ttft), ttft_n=len(ttft),
         itl_ms=percentiles(itl), itl_n=len(itl))
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": served.memory_peak_bytes}
    if trace:
        red = devtrace.reduce(served.trace)
        device.update(busy_s=red["busy_s"], window_s=red["window_s"])
        metrics = per_layer(cell, served, device, peak, bench_dir)
    else:
        metrics = end_to_end(served, seconds, clock["setup_s"])
    units = {m["name"]: m["unit"] for m in cell.end_to_end + cell.per_layer}
    wanted = cell.per_layer if trace else cell.end_to_end
    t0 = time.monotonic()
    c = checks(cell, served, weights, seed)
    diag(check_s=time.monotonic() - t0)
    window = _in_window(served)
    out = {"correct": is_correct(c), "attempted": len(window),
           "failed": c["requests_incomplete"]["value"],
           "metrics": {m["name"]: {"value": metrics[m["name"]],
                                   "unit": units[m["name"]]}
                       for m in wanted if m["name"] in metrics},
           "device": device}
    if trace:
        out["breakdown"] = red["breakdown"]
    out["checks"] = c
    return out


def report(out: dict) -> None:
    for k, v in out["checks"].items():
        print(f"check {k} {v['value']} limit {v['limit']}", file=sys.stderr)
    sys.stderr.flush()
    print(json.dumps(out), flush=True)
