"""One run of one cell: build the served configuration from its file,
warm it up, drive the cell's traffic for ``--seconds`` through
``Engine.submit`` / ``Engine.step``, then check what it served against
the configuration's plain reference and print one JSON line.

Everything a cell is made of is found by name: the cell in
``BENCHMARK.json``, its configuration in ``bench/configs/<name>.json``,
its reference in ``bench/references/<reference>.py``, its traffic in
``bench/traffic/<name>.json`` and each per-layer metric's reader in
``bench/metrics/<metric>.py``.  Adding a cell, a configuration, a mix or
a metric adds files and edits none.

The window is open-loop: every request of the schedule is submitted
before the window opens with its due time as ``arrival``, and is timed
from that due time.  A pre-roll of the same traffic (the mix's
``warm_s``) runs first, unmeasured, so the window opens on a loaded
server.  After the window no request arrives; the run goes on until each
request due in the window has its first token, for at most ``GRACE_S``
seconds, and counts one whose first token never came as failed.
"""

from __future__ import annotations

import dataclasses
import gc
import importlib.util
import json
import os
import sys
import tempfile
import time
from pathlib import Path

import numpy as np

from bench import traffic

__all__ = ["Cell", "load_cell", "make_cell", "run", "RunError"]

BENCH_DIR = Path(__file__).resolve().parent
ROOT = BENCH_DIR.parent
# a request due in the window whose first token has not come this long
# after the window closes has failed
GRACE_S = 60.0
# the reference is run over up to CHECK_REQUESTS finished requests (the
# longest always among them) and compares the first CHECK_FIRST tokens
# each served: past those, random weights settle into loops that every
# precision continues alike, and such tokens tell nothing
CHECK_REQUESTS = 16
CHECK_FIRST = 48
# the reference runs each request over its prompt and compared tokens,
# padded up to a multiple of this (a causal pass: padding after the last
# compared position changes nothing before it), so it compiles a few
# lengths and does no work past the compared tokens
CHECK_BUCKET = 256


class RunError(RuntimeError):
    """The run cannot produce a result (no chip, unknown device, a
    configuration the program does not serve as stated)."""


@dataclasses.dataclass
class Cell:
    name: str
    chips: int
    config: dict
    mix: dict
    rate: float             # requests per second
    end_to_end: list        # the metric entries the cell reports
    per_layer: list


def _load_module(path: Path, name: str):
    spec = importlib.util.spec_from_file_location(name, path)
    if spec is None or not path.is_file():
        raise RunError(f"no module at {path}")
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _applies(metric: dict, cell: str) -> bool:
    return "workloads" not in metric or cell in metric["workloads"]


def load_cell(name: str, root: Path = ROOT) -> Cell:
    """The cell ``name`` of ``<root>/BENCHMARK.json`` with its
    configuration, traffic mix and the metrics it reports."""
    bench_file = Path(root) / "BENCHMARK.json"
    if not bench_file.is_file():
        raise RunError(f"no BENCHMARK.json at {bench_file}")
    spec = json.loads(bench_file.read_text())
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise RunError(f"no workload {name!r} in {bench_file}")
    w = cells[name]
    return make_cell(w["config"], w["traffic"], root, name=name,
                     chips=int(w["chips"]),
                     end_to_end=[m for m in spec["end_to_end"]
                                 if _applies(m, name)],
                     per_layer=[m for m in spec["per_layer"]
                                if _applies(m, name)])


def make_cell(config: str, mix: str, root: Path = ROOT, *, name=None,
              chips: int = 1, end_to_end=(), per_layer=()) -> Cell:
    """The cell of configuration ``config`` under traffic ``mix``, read
    from their files (for a tool, the cell need not be in
    ``BENCHMARK.json``)."""
    bench_dir = Path(root) / "bench"
    cfg_path = bench_dir / "configs" / f"{config}.json"
    if not cfg_path.is_file():
        raise RunError(f"no configuration file {cfg_path}")
    mix_params = traffic.load_mix(mix, bench_dir / "traffic")
    return Cell(name=name or f"{config}.{mix}", chips=chips,
                config=json.loads(cfg_path.read_text()), mix=mix_params,
                rate=traffic.rate_for(mix_params, config),
                end_to_end=list(end_to_end), per_layer=list(per_layer))


def _pct(values, q) -> float:
    return float(np.percentile(np.asarray(values, np.float64), q))


# ---------------------------------------------------------------------------
# the program under test
# ---------------------------------------------------------------------------

def _serve_module(root: Path):
    src = str(Path(root) / "src")
    if src not in sys.path:
        sys.path.insert(0, src)
    try:
        from repro.launch import serve
    except ImportError as e:
        raise RunError(f"the program is not in this checkout ({e})") from e
    return serve


def _launcher_args(serve, config: dict, quant: str | None = None):
    """The launcher's own flags for the configuration: only the model,
    its widths, the quant mode (``quant`` where given) and backend, the
    paged cache and its sizes; every other knob keeps the program's
    default."""
    s = config["serving"]
    argv = ["--arch", config["arch"], "--quant", quant or s["quant"],
            "--quant-backend", s["quant_backend"],
            "--cache-mode", s["cache_mode"],
            "--page-size", str(s["page_size"]),
            "--batch", str(s["slots"]),
            "--num-pages", str(s.get("num_pages", 0)),
            "--prompt-len", str(s["prefill_len"]),
            "--new-tokens", str(s["max_len"] - s["prefill_len"])]
    if config["full_width"]:
        argv.append("--full-width")
    return serve.build_parser().parse_args(argv)


def _check_model(cfg, config: dict) -> None:
    """The program's model configuration must be the file's."""
    m = config["model"]
    want = {"n_layers": m["num_hidden_layers"], "d_model": m["hidden_size"],
            "n_heads": m["num_attention_heads"],
            "n_kv_heads": m["num_key_value_heads"],
            "head_dim": m["head_dim"], "d_ff": m["intermediate_size"],
            "vocab_size": m["vocab_size"], "norm_eps": m["rms_norm_eps"],
            "rope_theta": m["rope_theta"],
            "tie_embeddings": m["tie_word_embeddings"],
            "qk_norm": config["qk_norm"], "act": m["hidden_act"]}
    got = {k: getattr(cfg, k) for k in want}
    bad = {k: (got[k], v) for k, v in want.items() if got[k] != v}
    if bad:
        raise RunError(f"the program's {config['arch']} differs from the "
                       f"configuration file (program, file): {bad}")


def _check_tree(jax, weights, cfg) -> None:
    from repro.models import model_init
    want = jax.eval_shape(lambda: model_init(jax.random.PRNGKey(0), cfg))
    sig = lambda t: jax.tree_util.tree_map(  # noqa: E731
        lambda a: (tuple(a.shape), str(a.dtype)), t)
    if sig(weights) != sig(want):
        raise RunError("the benchmark's weight tree differs from the "
                       "program's parameter layout")


@dataclasses.dataclass
class Stack:
    """A configuration as the program serves it: the launcher module, its
    flags and model configuration, and the configuration's reference."""
    config: dict
    serve: object
    args: object
    cfg: object
    ref: object


def prepare(cell: Cell, root: Path = ROOT, *, control: bool = False) -> Stack:
    """The cell's configuration as the program serves it; with
    ``control``, in the lower precision the configuration names as its
    control."""
    config = cell.config
    serve = _serve_module(root)
    args = _launcher_args(serve, config,
                          config["control"]["quant"] if control else None)
    cfg = serve.model_config(args)
    _check_model(cfg, config)
    ref = _load_module(Path(root) / "bench" / "references"
                      / f"{config['reference']}.py",
                      f"bench_ref_{config['reference']}")
    return Stack(config, serve, args, cfg, ref)


def build(jax, stack: Stack, seed: int) -> tuple:
    """Draw the weights from ``seed``, build the engine through
    ``launch.serve.build`` and warm it up; returns ``(weights,
    engine)``."""
    model = stack.config["model"]
    weights = jax.block_until_ready(
        stack.ref.init_weights(model, stack.config["qk_norm"], seed))
    _check_tree(jax, weights, stack.cfg)
    _, _, engine = stack.serve.build(stack.args, params=weights)
    warm_up(engine, model["vocab_size"], seed)
    return weights, engine


# ---------------------------------------------------------------------------
# device
# ---------------------------------------------------------------------------

def load_peaks(kind: str, path: Path = BENCH_DIR / "peaks.json") -> dict:
    peaks = json.loads(Path(path).read_text())
    if kind not in peaks:
        raise RunError(f"device kind {kind!r} is not in {path}")
    return peaks[kind]


def device_info(jax, chips: int, require_tpu: bool = True):
    devices = jax.devices()
    d = devices[0]
    if require_tpu and d.platform != "tpu":
        raise RunError(f"needs a TPU; JAX found {d.platform!r}")
    if len(devices) < chips:
        raise RunError(f"the cell needs {chips} chip(s); JAX found "
                       f"{len(devices)}")
    return devices


def _peak_bytes(devices) -> int | None:
    peaks = [(dev.memory_stats() or {}).get("peak_bytes_in_use")
             for dev in devices]
    peaks = [p for p in peaks if p is not None]
    return max(peaks) if peaks else None


# ---------------------------------------------------------------------------
# the run
# ---------------------------------------------------------------------------

class _CompileWatch:
    """Counts programs traced or compiled while ``armed``."""
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/backend_compile_duration")

    def __init__(self, jax):
        self.armed = False
        self.count = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    def _on(self, name, _secs, **_kw):
        if self.armed and name in self.EVENTS:
            self.count += 1


def warm_up(engine, vocab: int, seed: int) -> None:
    """Compile the cell's own shapes: one prefill at the slot budget
    and one decode chunk, through the engine's own path."""
    rng = np.random.default_rng([seed, 0xC0])
    engine.submit(rng.integers(0, vocab, 16, dtype=np.int32),
                  engine.scfg.decode_chunk + 1)
    engine.run()
    engine.reset()


def schedules(cell: Cell, *, seed: int, seconds: float, vocab: int,
              rate: float | None = None) -> tuple:
    """The pre-roll's schedule (``warm_s`` seconds of the same traffic
    before the window opens, so that the window opens on a loaded
    server) and the window's."""
    rate = cell.rate if rate is None else rate
    pre = traffic.draw(cell.mix, rate=rate, seed=(seed, 1),
                       seconds=cell.mix["warm_s"], vocab_size=vocab)
    win = traffic.draw(cell.mix, rate=rate, seed=seed, seconds=seconds,
                       vocab_size=vocab)
    return pre, win


def _running(engine) -> list:
    # the engine has no public view of the requests in its slots; the
    # program's router reads the same attribute
    return [r for r in getattr(engine, "_slots", ()) if r is not None]


def _window_stats(s0: dict, s1: dict) -> dict:
    """Engine counters over the window: differences of the counters,
    and the mean concurrency over the window's decode chunks."""
    n0, n1 = s0["decode_chunks"], s1["decode_chunks"]
    chunks = n1 - n0
    running = s1["concurrency"] * n1 - s0["concurrency"] * n0
    return {"decode_chunks": chunks,
            "prefill_tokens": s1["prefill_tokens"] - s0["prefill_tokens"],
            "preemptions": s1["preemptions"] - s0["preemptions"],
            "concurrency": running / chunks if chunks else 0.0}


def drive(engine, pre, win, warm_s: float, seconds: float, *,
          on_open=None, on_close=None, span=None) -> dict:
    """Serve the pre-roll for ``warm_s`` seconds, then the window for
    ``seconds``: every request is submitted up front with its due time
    as ``arrival``.  After the window no request arrives; the run goes
    on until every request due in the window has its first token, or
    for ``GRACE_S``.  Returns one record per request (times in seconds
    from the window's opening), the engine's counters over the window,
    and when the last step started inside the window began (``t_last``)
    and returned (``t_close``)."""
    from contextlib import nullcontext
    span = span or (lambda _name: nullcontext())
    ids = ([(engine.submit(p, int(n), arrival=float(a)), p, False)
            for p, n, a in zip(pre.prompts, pre.output_lens, pre.arrivals)]
           + [(engine.submit(p, int(n), arrival=warm_s + float(a)), p, True)
              for p, n, a in zip(win.prompts, win.output_lens,
                                 win.arrivals)])
    t0 = time.perf_counter()
    engine.start(t0)
    last = [t0]                 # when the latest step started

    def serve_until(t_end):
        while time.perf_counter() < t_end:
            last[0] = time.perf_counter()
            with span("bench.step"):
                busy = engine.step()
            if not busy:
                time.sleep(max(0.0, min(0.05, t_end - time.perf_counter())))

    with span("bench.warm"):
        serve_until(t0 + warm_s)
    t_open = time.perf_counter()
    if on_open is not None:
        on_open()
    s0 = dict(engine.stats)
    with span("bench.window"):
        serve_until(t_open + seconds)
    t_close = time.perf_counter()
    last_start = last[0]
    if on_close is not None:
        on_close()
    s1 = dict(engine.stats)
    window_ids = {rid for rid, _, w in ids if w}
    done = {}
    while time.perf_counter() - t_close < GRACE_S:
        done.update(engine.drain())
        started = {r.id for r in _running(engine)} | set(done)
        if window_ids <= started or not engine.step():
            break
    done.update(engine.drain())
    live = {r.id: r for r in _running(engine)}
    shift = t_open - t0
    reqs = []
    for (rid, prompt, in_window), n in zip(
            ids, list(pre.output_lens) + list(win.output_lens)):
        r = done.get(rid) or live.get(rid)
        rec = {"prompt": prompt, "prompt_len": int(len(prompt)),
               "output_len": int(n), "window": in_window,
               "done": rid in done, "t_first": None, "t_tokens": [],
               "tokens": []}
        if r is not None and r.t_first >= 0:
            rec.update(t_first=r.t_first - shift,
                       t_tokens=[t - shift for t in r.t_tokens],
                       tokens=list(r.tokens))
        reqs.append(rec)
    # due times on the same clock: the window opens when the pre-roll's
    # last step returns, a little after warm_s
    for rec, a in zip([r for r in reqs if r["window"]], win.arrivals):
        rec["arrival"] = warm_s + float(a) - shift
    return {"requests": reqs, "stats": _window_stats(s0, s1),
            "t_last": last_start - t_open, "t_close": t_close - t_open}


def end_to_end(reqs: list, seconds: float, last: float,
               close: float) -> dict:
    """``tok_s``, ``ttft_p95_ms`` and ``tpot_p95_ms`` of one window.
    ``tok_s`` is the tokens made in the window's ``seconds`` over those
    seconds.  The window opens on a step's end; the step that runs over
    its close (from ``last`` to ``close``) stamps its tokens at its end,
    after the close, though the device made them all through its run,
    so they count by the share of that run inside the window.  (Counted
    whole or not at all, a decode chunk of some 70 tokens would swing
    the rate by whichever side of the close a step ended on.)  The tails
    are over the requests due in the window; one whose first token never
    came has failed and reads worse than any other (the longest any
    request could wait: the window plus the grace).  A request still
    decoding when the run stops has its time per token from the tokens
    it emitted."""
    worst = seconds + GRACE_S
    inside = sum(1 for r in reqs for t in r["t_tokens"]
                 if 0.0 <= t <= seconds)
    over = sum(1 for r in reqs for t in r["t_tokens"]
               if seconds < t <= close)
    share = (seconds - last) / (close - last) if over else 0.0
    toks = inside + share * over
    ttft, tpot = [], []
    for r in (r for r in reqs if r["window"]):
        if r["t_first"] is None:
            ttft.append(worst)
            tpot.append(worst)
            continue
        ttft.append(r["t_first"] - r["arrival"])
        if len(r["t_tokens"]) > 1:
            tpot.append((r["t_tokens"][-1] - r["t_first"])
                        / (len(r["t_tokens"]) - 1))
    return {"tok_s": toks / seconds,
            "ttft_p95_ms": 1e3 * _pct(ttft, 95),
            "tpot_p95_ms": 1e3 * _pct(tpot, 95)}


# what the reference's gaps of the served tokens are read as: the widest,
# the mean, and the share of tokens that are not the reference's first
# choice; a configuration's "limits" name the ones its runs compare.  The
# mean of each request's tokens is given beside them, not compared.
READINGS = ("gap_max_std", "gap_mean_std", "off_argmax_share")


def gap_readings(per_request: list) -> dict:
    """The readings of the gaps of each compared request's tokens."""
    gaps = np.concatenate(per_request) if per_request else np.zeros(0)
    if not gaps.size:
        return {**dict.fromkeys(READINGS), "gap_mean_std_by_request": []}
    return {"gap_max_std": float(gaps.max()),
            "gap_mean_std": float(gaps.mean()),
            "off_argmax_share": float((gaps > 0).mean()),
            "gap_mean_std_by_request": [float(g.mean())
                                        for g in per_request]}


def sample(reqs: list, seed: int) -> list:
    """Finished requests for the reference: the one with the most served
    tokens, then others in an order drawn from the seed, up to
    ``CHECK_REQUESTS``."""
    fin = [i for i, r in enumerate(reqs) if r["done"]]
    if not fin:
        return []
    longest = max(fin, key=lambda i: (len(reqs[i]["tokens"]),
                                      reqs[i]["prompt_len"]))
    rng = np.random.default_rng([seed, 0xC4EC])
    rest = [int(i) for i in rng.permutation(fin) if i != longest]
    return [longest] + rest[:CHECK_REQUESTS - 1]


def forced(rec: dict, max_len: int) -> tuple:
    """One request teacher-forced: its prompt and served tokens (but the
    last) as one sequence, padded to a multiple of ``CHECK_BUCKET`` (at
    most ``max_len``), and the positions whose logits chose its first
    ``CHECK_FIRST`` served tokens."""
    prompt, toks = rec["prompt"], rec["tokens"][:CHECK_FIRST]
    n = len(prompt)
    used = n + len(toks) - 1
    seq = np.zeros(min(max_len, -(-used // CHECK_BUCKET) * CHECK_BUCKET),
                   np.int32)
    seq[:n] = prompt
    seq[n:used] = toks[:-1]
    return seq, slice(n - 1, used)


def served_gaps(ref, weights, config: dict, reqs: list, picked: list,
                max_len: int) -> list:
    """The reference's gaps (in std of its logits) of the first
    ``CHECK_FIRST`` served tokens of each picked request, one array per
    request."""
    gaps = []
    for i in picked:
        seq, at = forced(reqs[i], max_len)
        wanted = np.zeros(len(seq), np.int32)
        wanted[at] = reqs[i]["tokens"][:at.stop - at.start]
        g = np.asarray(ref.token_gaps(weights, config["model"],
                                      config["qk_norm"], seq, wanted))
        gaps.append(g[at])
    return gaps


def verdict(checks: dict, log) -> bool:
    """Whether every check holds: ``tokens_checked`` at least its limit,
    every other at most; each is logged beside its limit."""
    ok = True
    for name, c in checks.items():
        at_least = name == "tokens_checked"
        passed = c["value"] is not None and (
            c["value"] >= c["limit"] if at_least else c["value"] <= c["limit"])
        ok = ok and passed
        log(f"check {name} {c['value']} limit {'>=' if at_least else '<='} "
            f"{c['limit']}")
    return ok


def run(cell: Cell, *, seed: int, seconds: float, trace: bool,
        t_start: float, root: Path = ROOT, require_tpu: bool = True,
        peaks: dict | None = None, log=None, control: bool = False) -> dict:
    """One run of ``cell``; returns the result line's object.  With
    ``control`` the program serves in the configuration's control
    precision, and the same checks are expected to fail."""
    log = log or (lambda msg: print(msg, file=sys.stderr, flush=True))
    import jax

    devices = device_info(jax, cell.chips, require_tpu)
    kind = devices[0].device_kind
    if peaks is None:
        peaks = load_peaks(kind)
    if "limits" not in cell.config:
        raise RunError(f"the configuration of {cell.name!r} sets no limits "
                       f"for correct: it is inactive")
    stack = prepare(cell, root, control=control)
    config, ref = stack.config, stack.ref
    t = time.perf_counter()
    weights, engine = build(jax, stack, seed)
    t_build = time.perf_counter() - t
    watch = _CompileWatch(jax)
    vocab = config["model"]["vocab_size"]
    counts_before = dict(engine.compile_counts)
    pre, win = schedules(cell, seed=seed, seconds=seconds, vocab=vocab)
    span = None
    trace_dir = tempfile.mkdtemp(prefix="bench-trace-") if trace else None
    if trace:
        from jax.profiler import TraceAnnotation as span
    setup = {}

    def on_open():
        setup["s"] = time.perf_counter() - t_start
        watch.armed = True
        if trace:
            jax.profiler.start_trace(trace_dir)

    def on_close():
        if trace:
            jax.profiler.stop_trace()
        watch.armed = False

    log(f"weights, build and warm-up {t_build:.1f} s; pre-roll of "
        f"{len(pre)} requests in {cell.mix['warm_s']} s, then {len(win)} "
        f"due in {seconds} s at {cell.rate} req/s")
    try:
        drove = drive(engine, pre, win, cell.mix["warm_s"], seconds,
                      on_open=on_open, on_close=on_close, span=span)
    finally:
        watch.armed = False
    setup_s = setup["s"]
    log(f"set-up {setup_s:.1f} s")
    reqs = drove["requests"]
    peak = _peak_bytes(devices)
    counts_after = dict(engine.compile_counts)
    attempted = sum(r["window"] for r in reqs)
    failed = sum(r["window"] and r["t_first"] is None for r in reqs)
    e2e = end_to_end(reqs, seconds, drove["t_last"], drove["t_close"])
    e2e["setup_s"] = setup_s
    e2e["peak_hbm_gb"] = (peak or 0) / 1e9
    ctx = {"cell": cell, "config": config, "seconds": seconds,
           "requests": reqs, "stats": drove["stats"], "peaks": peaks,
           "scfg": engine.scfg, "t_close": drove["t_close"],
           "trace": None}
    # the program's state goes before the reference runs
    del engine
    gc.collect()
    result = {"correct": None, "attempted": attempted, "failed": failed}
    if trace:
        from bench import trace as tr
        ctx["trace"] = tr.load(trace_dir)
        _rmtree(trace_dir)
        metrics = {}
        for m in cell.per_layer:
            reader = _load_module(Path(root) / "bench" / "metrics"
                                  / f"{m['name']}.py",
                                  f"bench_metric_{m['name']}")
            value = reader.read(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    else:
        metrics = {m["name"]: {"value": float(e2e[m["name"]]),
                               "unit": m["unit"]}
                   for m in cell.end_to_end}
    result["metrics"] = metrics
    result["device"] = {"platform": devices[0].platform, "kind": kind,
                        "count": len(devices), "memory_peak_bytes": peak}
    if trace:
        t = ctx["trace"]
        result["device"]["busy_s"] = t.busy_s if t else 0.0
        result["device"]["window_s"] = t.window_s if t else 0.0
        if t is not None:
            result["breakdown"] = t.breakdown()

    t = time.perf_counter()
    picked = sample(reqs, seed)
    gaps = served_gaps(ref, weights, config, reqs, picked,
                       config["serving"]["max_len"])
    readings = gap_readings(gaps)
    n_tokens = sum(g.size for g in gaps)
    log(f"reference over {len(picked)} requests, {n_tokens} tokens: "
        f"{time.perf_counter() - t:.1f} s")
    short = sum(r["done"] and len(r["tokens"]) != r["output_len"]
                for r in reqs)
    limits = config["limits"]
    checks = {name: {"value": readings[name], "limit": limit}
              for name, limit in limits.items() if name in READINGS}
    checks["tokens_checked"] = {"value": int(n_tokens),
                                "limit": limits["tokens_checked"]}
    checks["short_requests"] = {"value": int(short), "limit": 0}
    checks["window_compiles"] = {
        "value": int(watch.count + (counts_after != counts_before)),
        "limit": 0}
    result["correct"] = verdict(checks, log)
    result["readings"] = readings
    result["checks"] = checks
    return result


def _rmtree(path) -> None:
    import shutil
    shutil.rmtree(path, ignore_errors=True)


def cache_dir(root: Path = ROOT) -> str:
    """JAX's persistent compilation cache: the directory
    ``JAX_COMPILATION_CACHE_DIR`` names where it is set, else a fixed
    directory inside the checkout.  Set before JAX is imported, so JAX
    and the program's own ``enable_compile_cache`` both take it."""
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or str(
        Path(root) / ".jax_cache")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = path
    return path
