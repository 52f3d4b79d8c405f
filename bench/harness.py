"""One run of one cell: set-up, the measured window, the check against the
plain reference, and the readings that the result line carries.

``run_cell`` is everything but the look for a chip, so that the tests can
drive it on the CPU at small widths; ``run.py`` adds that look.
"""

from __future__ import annotations

import dataclasses
import gc
import os
import shutil
import tempfile
import time
from typing import Any

import numpy as np

from bench import loop, reference
from bench import trace as trace_mod
from bench.model_spec import ModelSpec
from bench.spec import ROOT, Cell, SpecError, metric_reader
from bench.traffic import RequestStream
from repro.launch.common import random_params, use_compile_cache
from repro.launch.serve import make_engine

CHECK_ROWS = 8          # requests compared with the reference per run
TRACE_SECONDS = 20.0    # the traced part of a window: its last steps
REF_BLOCK = 4           # rows per reference call
COMPILE_EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
                  "/jax/core/compile/jaxpr_to_mlir_module_duration",
                  "/jax/core/compile/backend_compile_duration")
BACKEND_COMPILE = COMPILE_EVENTS[2]


class CompileClock:
    """Seconds JAX spends tracing, lowering and compiling, and the number of
    backend compiles."""

    def __init__(self):
        import jax
        self.seconds = 0.0
        self.compiles = 0
        jax.monitoring.register_event_duration_secs_listener(self._on_event)

    def _on_event(self, event: str, duration: float, **_) -> None:
        if event in COMPILE_EVENTS:
            self.seconds += duration
        if event == BACKEND_COMPILE:
            self.compiles += 1


class Tracer:
    """The profiler over the window's last steps.  ``start`` runs between two
    steps, when the device is idle, and opens the ``bench.traced`` span;
    ``stop``, after the window's close, ends both and reduces the trace."""

    def __init__(self):
        self.dir = tempfile.mkdtemp(prefix="bench_trace_")
        self.span = None
        self.seconds: dict[str, float] = {}

    def start(self) -> None:
        import jax
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(self.dir, profiler_options=opts)
        self.span = jax.profiler.TraceAnnotation(trace_mod.WINDOW_SPAN)
        self.span.__enter__()

    def stop(self) -> trace_mod.Reduction:
        import jax
        try:
            if self.span is None:
                raise RuntimeError("the window ended before its trace began")
            self.span.__exit__(None, None, None)
            t0 = time.perf_counter()
            jax.profiler.stop_trace()
            t1 = time.perf_counter()
            raw = trace_mod.load(trace_mod.find_xplane(self.dir))
            self.seconds = {"stop_s": t1 - t0,
                            "load_s": time.perf_counter() - t1}
        finally:
            shutil.rmtree(self.dir, ignore_errors=True)
        return trace_mod.reduce(raw)


@dataclasses.dataclass
class RunRecord:
    """What a per-layer metric reader reads."""
    model: ModelSpec
    peaks: dict[str, Any]
    window: loop.Window
    counters: dict[str, int]
    trace: trace_mod.Reduction | None


def use_cache() -> None:
    """Keep JAX's persistent compilation cache at a fixed path inside the
    checkout, unless ``JAX_COMPILATION_CACHE_DIR`` names one; cache every
    program, so that only a checkout's first run compiles."""
    import jax
    os.environ.setdefault("JAX_COMPILATION_CACHE_DIR",
                          str(ROOT / ".jax_cache"))
    use_compile_cache()
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)


_FIELDS = (   # configuration file key -> ModelConfig attribute
    ("num_hidden_layers", "n_layers"), ("hidden_size", "d_model"),
    ("num_attention_heads", "n_heads"), ("num_key_value_heads", "n_kv_heads"),
    ("head_dim", "head_dim"), ("intermediate_size", "d_ff"),
    ("vocab_size", "vocab_size"), ("rope_theta", "rope_theta"),
    ("norm_eps", "norm_eps"), ("tie_word_embeddings", "tie_embeddings"),
    ("dtype", "dtype"))


def program_config(config: dict[str, Any]):
    """The program's ``ModelConfig`` for a configuration file, checked key by
    key against the file, which is what the reference and costs read."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(config["arch"]),
                              **config.get("overrides", {}))
    want = {a: config[k] for k, a in _FIELDS}
    window = config.get("sliding_window") or 0
    # a window no shorter than the longest sequence masks nothing, so the
    # program may run it as full attention
    full = cfg.sliding_window == 0 and window >= config["max_len"]
    want["sliding_window"] = 0 if full else window
    want["norm"] = {"layer_norm": "ln", "rms_norm": "rms"}[config["norm"]]
    want["act"] = {"gelu_pytorch_tanh": "gelu",
                   "silu": "silu"}[config["hidden_act"]]
    want["attn_bias"] = want["mlp_bias"] = bool(config.get("use_bias", False))
    wrong = {a: (v, getattr(cfg, a)) for a, v in want.items()
             if getattr(cfg, a) != v}
    if wrong:
        raise SpecError(f"configuration file and program disagree "
                        f"(file, program): {wrong}")
    return cfg


def _stats(eng) -> dict[str, int]:
    """Every integer counter of the engine's stats; one the program lacks
    is absent, so that its readers find nothing."""
    return {k: v for k, v in dataclasses.asdict(eng.stats).items()
            if isinstance(v, int)}


def _check_set(w: loop.Window, seed: int) -> list[loop.Sent]:
    """The requests compared with the reference: those finished in the
    window or holding tokens at its close, drawn from the seed, the one
    with the most served tokens always among them."""
    pool = sorted(w.finished + w.in_flight,
                  key=lambda s: (-len(s.req.generated), s.req.req_id))
    if len(pool) <= CHECK_ROWS:
        return pool
    rng = np.random.default_rng([seed, 1])
    rest = rng.choice(len(pool) - 1, CHECK_ROWS - 1, replace=False) + 1
    return [pool[0]] + [pool[i] for i in sorted(rest)]


def compare(m: ModelSpec, seed: int, served: list[tuple[np.ndarray, list]],
            max_len: int, max_out: int, control: bool = False):
    """Logit gaps of each (prompt, served tokens) pair against the plain
    reference; with ``control`` also the fp8 control's at the same positions.
    Returns (per-row gap arrays, per-row control gap arrays, tokens outside
    the vocab)."""
    import jax.numpy as jnp
    w = reference.init_weights(m, seed)
    gaps, cgaps, outside = [], [], 0
    for i in range(0, len(served), REF_BLOCK):
        block = served[i:i + REF_BLOCK]
        tokens = np.zeros((REF_BLOCK, max_len), np.int32)
        pos = np.zeros((REF_BLOCK, max_out), np.int32)
        got = np.zeros((REF_BLOCK, max_out), np.int32)
        mask = np.zeros((REF_BLOCK, max_out), bool)
        for r, (prompt, toks) in enumerate(block):
            toks = np.asarray(toks, np.int64)
            outside += int(((toks < 0) | (toks >= m.vocab)).sum())
            seq = np.concatenate([prompt, toks[:-1]])
            tokens[r, :len(seq)] = np.clip(seq, 0, m.vocab - 1)
            n = len(toks)
            pos[r, :n] = len(prompt) - 1 + np.arange(n)
            got[r, :n] = np.clip(toks, 0, m.vocab - 1)
            mask[r, :n] = True
        g, c = reference.readings(w, m, jnp.asarray(tokens), jnp.asarray(pos),
                                  jnp.asarray(got), control)
        g, c = np.asarray(g), np.asarray(c)
        for r in range(len(block)):
            gaps.append(g[r][mask[r]])
            cgaps.append(c[r][mask[r]])
    del w
    return gaps, cgaps, outside


def gap_readings(rows: list[np.ndarray]) -> dict[str, float | None]:
    """The numbers a cell's limits may name, over every compared token:
    the widest gap and the mean gap."""
    if not rows or not sum(len(r) for r in rows):
        return {"worst_logit_gap": None, "mean_logit_gap": None}
    flat = np.concatenate(rows)
    return {"worst_logit_gap": float(flat.max()),
            "mean_logit_gap": float(flat.mean())}


def live_bytes() -> int:
    """Bytes of the arrays still alive, once the garbage is collected."""
    import jax
    gc.collect()
    return sum(a.nbytes for a in jax.live_arrays())


def run_cell(cell: Cell, seed: int, seconds: float, traced: bool, *,
             peaks: dict[str, Any], device=None, t_start: float | None = None,
             control: bool = False,
             log=print) -> dict[str, Any]:
    """One run; returns the result line's fields (``device`` left to the
    caller but for the peak memory and the trace's seconds)."""
    import jax

    t_start = time.perf_counter() if t_start is None else t_start
    clock = CompileClock()
    use_cache()
    conf, mix = cell.config, cell.traffic
    cfg = program_config(conf)
    m = ModelSpec.from_config(conf)
    max_batch, max_len = conf["max_batch"], conf["max_len"]

    t0 = time.perf_counter()
    params = jax.block_until_ready(random_params(cfg, seed))
    log(f"init: wall_s={time.perf_counter() - t0} compile_s={clock.seconds}")
    per_seq = -(-max_len // cfg.kv_page_tokens)
    exact = max_batch * per_seq
    frames = round(mix["pool_share"] * exact)
    eng = make_engine(cfg, params, max_batch=max_batch, max_len=max_len,
                      pool_frames=None if frames == exact else frames,
                      temperature=0.0)
    stream = RequestStream(mix, cfg.vocab_size, seed)
    span = jax.profiler.TraceAnnotation if traced else loop.no_span
    closed = loop.ClosedLoop(eng, stream, max_batch, span=span)
    t0, c0 = time.perf_counter(), clock.seconds
    t_open = closed.fill()
    log(f"fill: wall_s={t_open - t0} compile_s={clock.seconds - c0} "
        f"prompt_tokens={sum(len(s.req.prompt) for s in closed.live)}")
    setup_s = t_open - t_start

    stats0, compiles0 = _stats(eng), clock.compiles
    tracer = Tracer() if traced else None
    window = closed.run(t_open, seconds, TRACE_SECONDS,
                        tracer.start if tracer else None)
    reduction = None
    if tracer:
        t0 = time.perf_counter()
        reduction = tracer.stop()
        log(f"trace: steps={len(window.traced_steps)} "
            f"window_s={reduction.window_s} busy_s={reduction.busy_s} "
            f"read_s={time.perf_counter() - t0} "
            f"{tracer.seconds}")
    compiles_in_window = clock.compiles - compiles0
    counters = {k: v - stats0[k] for k, v in _stats(eng).items()}
    mem = (device.memory_stats() or {}) if device is not None else {}
    peak_bytes = int(mem.get("peak_bytes_in_use", 0))
    slowest = sorted(window.gaps_s)[-24:]
    log(f"window: seconds={window.seconds} steps={len(window.steps)} "
        f"tokens={window.tokens} first_tokens={len(window.ttft_s)} "
        f"gaps={len(window.gaps_s)} compiles_in_window={compiles_in_window} "
        f"slowest_gaps_ms={[round(1e3 * g, 1) for g in slowest]} "
        f"counters={counters}")

    checked = [(s.req.prompt, list(s.req.generated))
               for s in _check_set(window, seed)]
    del closed, eng, params, stream
    log(f"freed: live_bytes={live_bytes()}")

    t0 = time.perf_counter()
    gaps, cgaps, outside = compare(m, seed, checked, max_len,
                                   mix["output_tokens"]["max"], control)
    log(f"reference: wall_s={time.perf_counter() - t0} rows={len(gaps)} "
        f"tokens={sum(len(t) for _, t in checked)} "
        f"row_worst={[float(g.max()) for g in gaps]}")
    readings = {"program": gap_readings(gaps)}
    if control:
        # the control stands in the program's place: its first choices at
        # the same positions are what the limits judge
        log(f"control: row_worst={[float(g.max()) for g in cgaps]}")
        gaps = cgaps
        readings["control"] = gap_readings(gaps)
    values = gap_readings(gaps)
    log(f"readings: {readings}")
    checks = {"tokens_outside_vocab": {"value": outside, "limit": 0,
                                       "at": "most"}}
    for name, lim in cell.limits.items():
        checks[name] = {"value": values[name], "limit": lim["limit"],
                        "at": "most"}
    if mix["pool_share"] < 1:
        checks["fault_page_ins"] = {"value": counters["fault_page_ins"],
                                    "limit": 1, "at": "least"}

    def ok(c):
        if c["value"] is None:
            return False
        return c["value"] <= c["limit"] if c["at"] == "most" \
            else c["value"] >= c["limit"]
    correct = bool(gaps) and all(ok(c) for c in checks.values())
    # a compared request fails where its own tokens break a limit
    failed = sum(any(gap_readings([g])[k] > v["limit"]
                     for k, v in cell.limits.items()) for g in gaps)

    if traced:
        record = RunRecord(m, peaks, window, counters, reduction)
        metrics = {}
        for mt in cell.per_layer:
            v = metric_reader(mt.name, cell.root)(record)
            if v is not None:
                metrics[mt.name] = {"value": v, "unit": mt.unit}
    else:
        values = loop.end_to_end(window)
        values["setup_s"] = setup_s
        metrics = {mt.name: {"value": values[mt.name], "unit": mt.unit}
                   for mt in cell.end_to_end if values[mt.name] is not None}
    out = {"correct": correct, "attempted": window.attempted,
           "failed": failed + (outside > 0),
           "metrics": metrics,
           "device": {"memory_peak_bytes": peak_bytes}}
    if reduction is not None:
        out["device"].update(busy_s=reduction.busy_s,
                             window_s=reduction.window_s)
        out["breakdown"] = {
            "device_ops": [list(x) for x in reduction.device_ops],
            "idle_gaps": [list(x) for x in reduction.idle_gaps]}
    out["readings"] = readings
    out["checks"] = checks
    return out
