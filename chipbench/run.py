"""Run one benchmark cell once on the chip this process finds.

    python3 chipbench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>
    JAX_PLATFORMS=cpu python3 chipbench/run.py --workload <cell> --seed 1 \
        --seconds 3 --trace 0 --reduced          # CPU rehearsal, tiny config

The cell (``BENCHMARK.json`` ``workloads``) names a configuration
(``chipbench/configs/<config>.json``) and a traffic mix
(``chipbench/traffic/<traffic>.json``); ``chipbench/workloads/<cell>.json``
sizes the engine and the load.  Set-up builds ``repro.serving.engine.Engine``
as ``repro.launch.serve`` does (backend ``cxl``, mode ``sac``, prefetch and
radix on, weights from the seed), warms every prompt length the mix sends,
and fills every slot with a client's first request.  The window then drives
``Engine.step()`` for ``--seconds`` on the host clock, each client sending
its next request when its last one ends; the engine's modelled latencies
are never read.

``--trace 0`` reports the cell's end-to-end metrics; ``--trace 1`` records
a profiler trace of the window's last ``TRACED_S`` seconds and reports its
per-layer metrics, each read by ``chipbench/metrics/<name>.py``.  After
the window the served
tokens of a sample of the finished requests are compared with the plain
reference (``chipbench/reference/<kind>.py``), which decides ``correct``.
``--control`` puts the reference computed one precision lower (fp8) in the
program's place for that comparison; it has to come out not correct.  The last
line of standard output is the result as one JSON object.  A run that
finds no TPU, fewer chips than the cell asks for, or a device kind missing
from ``chipbench/peaks.json`` exits non-zero without a result.
"""
from __future__ import annotations

import time

T_PROCESS = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402
from types import SimpleNamespace  # noqa: E402
from typing import Optional  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "chipbench"
# the compile cache lives at a fixed path inside the checkout, so only a
# cell's first run there compiles and two checkouts share nothing
CACHE = ROOT / ".jax_cache"
sys.path[:0] = [str(ROOT), str(ROOT / "src")]

import jax  # noqa: E402
import numpy as np  # noqa: E402

from chipbench import scopes, stats  # noqa: E402
from chipbench.generator import Traffic  # noqa: E402
from chipbench.trace import WINDOW, Trace, find_xplane  # noqa: E402

WARM_ID = 10**9          # request ids of the warm-up requests
WARM_OUT = 2             # tokens each warm-up request decodes
# a traced run traces the last TRACED_S seconds of its window: on a v5e the
# profiler kept 4216118 device events of a 51-s window (about 440 of its
# 615 decode steps) and dropped the rest, and stopping it took some 30 us
# an event
TRACED_S = 20.0


class Refused(Exception):
    """The run cannot be measured here (no chip, unknown device)."""


def emit(**fields):
    print(json.dumps(fields), flush=True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str, reduced: bool) -> SimpleNamespace:
    """The cell's entry and every file it names, found by name."""
    bench = load_json(ROOT / "BENCHMARK.json")
    entry = {w["name"]: w for w in bench["workloads"]}.get(name)
    if entry is None:
        raise SystemExit(f"unknown workload {name!r}")
    cell = load_json(BENCH / "workloads" / f"{name}.json")
    if (cell["config"], cell["traffic"]) != (entry["config"], entry["traffic"]):
        raise SystemExit(f"{name}: the cell file and BENCHMARK.json disagree")
    conf = load_json(BENCH / "configs" / f"{entry['config']}.json")
    mix = load_json(BENCH / "traffic" / f"{entry['traffic']}.json")
    if reduced:
        small = cell["reduced"]
        cell = {**cell, **{k: small[k]
                           for k in ("slots", "max_ctx", "clients")}}
        cell["compare"] = {**cell["compare"], **small["compare"]}
        cell["limits"] = small["limits"]
        mix = {**mix, "requests": small["requests"], "round": small["round"]}
    return SimpleNamespace(name=name, bench=bench, entry=entry, cell=cell,
                           conf=conf, mix=mix)


# what the program runs for a name of a file's ``model`` block that is no
# field of its configuration (or a field it leaves None and derives)
PROGRAM_VALUES = {
    # ModelConfig.head_dim is None where the head is d_model // n_heads;
    # every layer uses ModelConfig.hd (src/repro/configs/base.py, ``def hd``)
    "head_dim": lambda cfg: cfg.hd,
    # every norm is rms_norm's default (src/repro/models/layers.py,
    # ``def rms_norm(x, gamma, eps=1e-6)``)
    "rms_norm_eps": lambda cfg: 1e-6,
    # the program always holds a head of its own (src/repro/models/
    # transformer.py, ``model_param_specs``: ``"lm_head": ParamSpec(...)``)
    "tie_word_embeddings": lambda cfg: False,
}


def program_value(cfg, key: str):
    """The value the program runs for ``key``: a field of the model's
    configuration, else of its SAC configuration, else
    :data:`PROGRAM_VALUES`; KeyError where the program has it nowhere."""
    fields = {f.name for f in dataclasses.fields(cfg)}
    if key in fields and (getattr(cfg, key) is not None
                          or key not in PROGRAM_VALUES):
        return getattr(cfg, key)
    if key in {f.name for f in dataclasses.fields(cfg.sac)}:
        return getattr(cfg.sac, key)
    if key in PROGRAM_VALUES:
        return PROGRAM_VALUES[key](cfg)
    raise KeyError(key)


def model_config(conf: dict, reduced: Optional[dict]):
    """The registry configuration with the file's overrides, checked
    against every size the file's ``model`` block states; returns ``(cfg,
    sizes)``, ``sizes`` holding the block's keys.  With ``reduced`` (the
    cell's rehearsal block), the registry's tiny configuration, its top-k
    set to the block's, and ``sizes`` holds its values."""
    from repro.configs import get_config
    cfg = dataclasses.replace(get_config(conf["arch"]), **conf["overrides"])
    if reduced:
        cfg = cfg.reduced()
        cfg = dataclasses.replace(
            cfg, sac=dataclasses.replace(cfg.sac, topk=reduced["topk"]))
    stated = conf["model"]
    sizes, unknown = {}, []
    for k in stated:
        try:
            sizes[k] = program_value(cfg, k)
        except KeyError:
            unknown.append(k)
    if unknown:
        raise SystemExit(f"{conf['name']}: the program has no {unknown} "
                         "(model block of the file)")
    if not reduced and sizes != stated:
        diff = {k: (sizes[k], stated[k]) for k in stated
                if sizes[k] != stated[k]}
        raise SystemExit(f"{conf['name']}: the program's configuration "
                         f"differs from the file (program, file): {diff}")
    return cfg, (sizes if reduced else stated)


def device_of(chips: int, reduced: bool, peaks: dict) -> dict:
    devs = jax.devices()
    d = devs[0]
    info = {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}
    if reduced:
        return info
    if d.platform != "tpu":
        raise Refused(f"the first device is {d.platform!r}, not a TPU "
                      "(--reduced is the CPU rehearsal)")
    if len(devs) < chips:
        raise Refused(f"the cell needs {chips} chips, JAX finds {len(devs)}")
    if d.device_kind not in peaks:
        raise Refused(f"device kind {d.device_kind!r} is not in "
                      "chipbench/peaks.json")
    return info


class CompileLog:
    """Backend compile seconds per jitted function and persistent-cache
    hits, from JAX's monitoring events."""

    BACKEND = "/jax/core/compile/backend_compile_duration"

    def __init__(self):
        self.backend_s = collections.Counter()
        self.count = 0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == self.BACKEND:
            self.backend_s[kw.get("fun_name", "?")] += secs
            self.count += 1

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        """Compiles since the last take; resets the counts."""
        big = {k: v for k, v in self.backend_s.most_common() if v >= 0.5}
        out = {"compiles": self.count,
               "backend_compile_total_s": sum(self.backend_s.values()),
               "backend_compile_s": big,
               "persistent_cache_hits": self.cache_hits}
        self.backend_s.clear()
        self.count = self.cache_hits = 0
        return out


class GcLog:
    """Collections of Python's garbage collector per generation, and the
    seconds they held the host."""

    def __init__(self):
        self.count, self.secs = [0, 0, 0], [0.0, 0.0, 0.0]
        self._t = None
        gc.callbacks.append(self._callback)

    def _callback(self, phase, info):
        if phase == "start":
            self._t = time.perf_counter()
        elif self._t is not None:
            g = info["generation"]
            self.count[g] += 1
            self.secs[g] += time.perf_counter() - self._t
            self._t = None

    def take(self) -> dict:
        """Collections since the last take; resets the counts."""
        out = {"gc_collections": self.count, "gc_s": self.secs}
        self.count, self.secs = [0, 0, 0], [0.0, 0.0, 0.0]
        return out


def wrap(obj, attr: str, span: str, before=None):
    """Replace ``obj.attr`` by a call inside a host span (traced runs)."""
    fn = getattr(obj, attr)

    def wrapped(*a, **kw):
        if before is not None:
            before(*a)
        with jax.profiler.TraceAnnotation(span):
            return fn(*a, **kw)
    setattr(obj, attr, wrapped)


def instrument(eng, win) -> str:
    """Wrap the engine's serving methods in host spans, note the prompt
    tokens of every prefill and the arguments of the last decode after the
    state, and start the profiler; returns the trace directory."""
    win.decode = eng._decode
    wrap(eng, "_fill_slots", "Engine._fill_slots")
    wrap(eng, "_admit_request", "Engine._admit_request")
    wrap(eng, "_prefill_one", "Engine._prefill_one",
         before=lambda params, toks: win.prefills.append(
             (time.monotonic(), int(toks.shape[1]))))
    wrap(eng, "_splice_state", "Engine._splice_state")
    wrap(eng, "_warm", "Engine._warm")
    wrap(eng, "_decode", "Engine._decode",
         before=lambda params, state, *rest: setattr(win, "decode_rest",
                                                     rest))
    trace_dir = tempfile.mkdtemp(prefix="chipbench-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(trace_dir, profiler_options=opts)
    return trace_dir


def decode_text(eng, win, log: CompileLog) -> dict:
    """The text of the compiled decode the traced window ran: the engine's
    jitted decode lowered on its parameters, its state (the last decode's
    output, so of the shapes every decode takes) and the last decode's
    other arguments; the executable comes from JAX's caches, and
    ``compiles`` counts a fresh compile (another program) if one was made.
    Empty where the window made no decode through a jitted function."""
    if not hasattr(win.decode, "lower") or win.decode_rest is None:
        return {"text": "", "compiles": 0, "seconds": 0.0}
    t = time.monotonic()
    log.take()
    text = win.decode.lower(eng.params, eng.state,
                            *win.decode_rest).compile().as_text()
    return {"text": text, "compiles": log.take()["compiles"],
            "seconds": time.monotonic() - t}


def build_engine(cfg, cell: dict, seed: int):
    from repro.serving.engine import Engine
    return Engine(cfg, slots=cell["slots"], max_ctx=cell["max_ctx"],
                  backend="cxl", mode="sac", prefetch=True, radix=True,
                  seed=seed)


def request(rid: int, prompt: np.ndarray, output_len: int):
    from repro.serving.request import Request
    # arrival 0 is at or below the engine's virtual clock: the engine's
    # arrival gate admits a request at the first free slot
    return Request(rid, 0.0, len(prompt), output_len, prompt)


def busy(eng) -> bool:
    return bool(eng.queue) or any(r is not None for r in eng.slot_req)


def warm_up(eng, traffic: Traffic, vocab: int):
    """Serve one short request of every prompt length the mix sends, so
    each shape the window uses is compiled (or loaded) here."""
    rng = np.random.default_rng(0)
    lengths = traffic.lengths()
    for i in range(0, len(lengths), eng.slots):
        batch = [request(WARM_ID + i + j,
                         rng.integers(0, vocab, n, dtype=np.int32), WARM_OUT)
                 for j, n in enumerate(lengths[i:i + eng.slots])]
        for r in batch:
            eng.submit(r)
        while busy(eng):
            eng.step()


class Window:
    """The measured loop: step, time every token, and send each client's
    next request when its last one ends."""

    def __init__(self, eng, traffic: Traffic):
        self.eng, self.traffic = eng, traffic
        self.records = {}                 # rid -> stats.Record
        self.live = {}                    # rid -> Request
        self.steps = []                   # per step: (end time, contexts)
        self.admitting = []               # per step: requests admitted
        self.prefills = []                # (host time, prompt tokens)
        self.finished_tokens = {}         # rid -> served tokens
        self.client_of = {}               # rid -> client
        self.sent = collections.Counter()  # client -> requests sent
        self.decode = None                # the jitted decode (traced runs)
        self.decode_rest = None           # its last call's args after state

    def send(self, c: int):
        item = self.traffic.request(c, self.sent[c])
        self.sent[c] += 1
        self.client_of[item.rid] = c
        req = request(item.rid, item.prompt, item.output_len)
        self.records[item.rid] = stats.Record(len(item.prompt))
        self.live[item.rid] = req
        self.eng.submit(req)

    def step(self):
        with jax.profiler.TraceAnnotation("chipbench.step"):
            finished = self.eng.step()
        t = time.monotonic()
        contexts, first = [], 0
        for rid, req in list(self.live.items()):
            rec = self.records[rid]
            if req.generated > len(rec.token_s):
                contexts.append(rec.prompt_len + len(rec.token_s))
                first += not rec.token_s
                rec.token_s.append(t)
        self.steps.append((t, contexts))
        self.admitting.append(first)
        for req in finished:
            self.live.pop(req.request_id, None)
            self.finished_tokens[req.request_id] = list(req.out_tokens)
            self.send(self.client_of[req.request_id])

    def fill(self):
        """Every client's first request, admitted and given its first
        token before the window opens."""
        for c in range(self.traffic.clients):
            self.send(c)
        self.step()

    def step_gaps(self, t0: float, t1: float) -> dict:
        """How the window's steps spread: the median step, and the steps
        that admit nothing yet take over twice as long (host pauses)."""
        ts = np.array([t for t, _ in self.steps])
        adm = np.array(self.admitting)
        inside = (ts[1:] > t0) & (ts[1:] <= t1)
        d, adm = np.diff(ts)[inside], adm[1:][inside]
        if not d.size:
            return {}
        med = float(np.median(d[adm == 0])) if (adm == 0).any() else 0.0
        slow = d[(adm == 0) & (d > 2 * med)]
        return {"step_median_s": med, "step_max_s": float(d.max()),
                "admitting_steps": int((adm > 0).sum()),
                "admitting_s": float(d[adm > 0].sum()),
                "slow_steps": int(slow.size),
                "slow_steps_excess_s": float((slow - med).sum())}

    def run(self, seconds: float, start_trace=None):
        """Step for ``seconds``; with ``start_trace``, call it when
        :data:`TRACED_S` of them are left and trace those.  Returns the
        window's start and end, and the traced part's start."""
        t0 = time.monotonic()
        t1 = t0 + seconds
        tt = t0 if start_trace is None else t1 - TRACED_S
        while time.monotonic() < tt:
            self.step()
        if start_trace is not None:
            start_trace()
            tt = time.monotonic()
        with jax.profiler.TraceAnnotation(WINDOW):
            while time.monotonic() < t1:
                self.step()
        return t0, t1, tt


def sample(candidates, n: int, seed: int):
    """``n`` of ``(rid, served tokens)`` drawn from the seed, always with
    the one served the most tokens."""
    if not candidates:
        return []
    order = sorted(candidates, key=lambda c: (-len(c[1]), c[0]))
    rest = order[1:]
    rng = np.random.default_rng([int(seed) % 2**63, 4])
    pick = rng.permutation(len(rest))[: max(n - 1, 0)]
    return [order[0]] + [rest[i] for i in sorted(pick)]


# what the comparison reads over every compared token: the widest gap
# between the reference's best logit and the served token's, and the mean
# gap.  A cell's ``limits`` name the readings it holds to a limit.
READINGS = {
    "logit_gap": lambda g: float(g.max()),
    "logit_gap_mean": lambda g: float(g.mean()),
}


def readings(gaps) -> dict:
    if not gaps:
        return {k: float("inf") for k in READINGS}
    g = np.concatenate(gaps)
    return {k: f(g) for k, f in READINGS.items()}


def compare(spec, sizes: dict, wseed: int, prompts: dict, picked,
            control: bool):
    """Per request, the reference's gap at every compared token, and the
    control's (with ``control``); plus the count of tokens out of vocab.
    The reference computes in the precision the configuration states."""
    ref = importlib.import_module(f"chipbench.reference.{spec.conf['reference']}")
    w = ref.make_weights(sizes, wseed)
    cw = ref.fp8_weights(w) if control else None
    gaps, cgaps, bad = [], [], 0
    rows = spec.cell["compare"]["decode_rows"]
    for rid, toks in picked:
        toks = toks[:rows]          # the first served tokens of each
        bad += sum(not 0 <= t < sizes["vocab"] for t in toks)
        toks = [min(max(int(t), 0), sizes["vocab"] - 1) for t in toks]
        out = ref.compare(sizes, w, prompts[rid], toks,
                          max_ctx=spec.cell["max_ctx"],
                          n_dec=rows,
                          control_w=cw, act=spec.conf["precision"])
        gaps.append(out["gap"])
        if control:
            cgaps.append(out["control_gap"])
    return gaps, cgaps, bad


def metric_entries(spec, kind: str):
    return [m for m in spec.bench[kind]
            if spec.name in m.get("workloads", [spec.name])]


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    ap.add_argument("--reduced", action="store_true",
                    help="CPU rehearsal at a tiny configuration; reports "
                         "no metric")
    ap.add_argument("--control", action="store_true",
                    help="judge the control (the reference in fp8) in the "
                         "program's place; it has to come out not correct")
    args = ap.parse_args(argv)

    spec = load_cell(args.workload, args.reduced)
    peaks = load_json(BENCH / "peaks.json")
    try:
        device = device_of(spec.entry["chips"], args.reduced, peaks)
    except Refused as e:
        print(f"chipbench: {e}", file=sys.stderr)
        return 2
    peak = peaks.get(device["kind"], {})
    jax.config.update("jax_compilation_cache_dir", str(CACHE))
    # set-up compiles dozens of sub-second programs; cache them all.  No
    # eviction: it needs every entry's access-time file, and one lost to
    # a process that exited mid-write stops all later writes
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_compilation_cache_max_size", -1)
    log = CompileLog()
    gc_log = GcLog()
    cfg, sizes = model_config(
        spec.conf, spec.cell["reduced"] if args.reduced else None)
    cell = spec.cell
    wseed = args.seed % 2**31
    traffic = Traffic(spec.mix, seed=args.seed, vocab=sizes["vocab"],
                      clients=cell["clients"])

    eng = build_engine(cfg, cell, wseed)
    warm_up(eng, traffic, sizes["vocab"])
    win = Window(eng, traffic)
    win.fill()
    # as a long-running server does after warm-up: set-up's objects (the
    # modules, traced programs, engine) go where the collector no longer
    # walks them, so a full collection in the window walks what it made
    gc.collect()
    gc.freeze()
    setup = log.take()
    gc_log.take()
    t_setup = time.monotonic()
    trace_dir = []
    t0, t1, tt = win.run(args.seconds, start_trace=(
        (lambda: trace_dir.append(instrument(eng, win))) if args.trace
        else None))
    in_window = log.take()
    in_window_gc = gc_log.take()
    dev = jax.devices()[0]
    mem = (dev.memory_stats() or {}).get("peak_bytes_in_use")
    trace = scope_ms = None
    if args.trace:
        t_stop = time.monotonic()
        jax.profiler.stop_trace()
        t_read = time.monotonic()
        trace = Trace(find_xplane(trace_dir[0]))
        shutil.rmtree(trace_dir[0], ignore_errors=True)
        stop_s, read_s = t_read - t_stop, time.monotonic() - t_read
        names = spec.conf.get("scopes", [])
        text = decode_text(eng, win, log)
        op_scope = scopes.op_scopes(text["text"], names)
        scope_ms, unmapped = scopes.scope_ms(trace, "jit_decode", op_scope,
                                             names)
        # a trace that lost events holds fewer decode executions than the
        # host made steps in the traced part
        emit(phase="trace", stop_s=stop_s, read_s=read_s,
             op_events=len(trace.ops),
             decode_executions=len(trace.executions("jit_decode")),
             steps_traced=sum(1 for t, _ in win.steps if tt < t <= t1),
             busy_s=trace.busy_s(), op_busy_s=trace.busy_s(ops=True),
             window_s=trace.window_s(), decode_ms_by_scope=scope_ms,
             ops_unmapped=unmapped, decode_text_s=text["seconds"],
             decode_text_compiles=text["compiles"],
             decode_text_ops=collections.Counter(op_scope.values()))
    records = list(win.records.values())
    emit(phase="setup", setup_s=t_setup - T_PROCESS, **setup)
    emit(phase="window", seconds=t1 - t0,
         steps=sum(1 for t, _ in win.steps if t0 < t <= t1),
         requests=len(records), finished=len(win.finished_tokens),
         compiles_in_window=in_window["compiles"],
         compile_s_in_window=in_window["backend_compile_total_s"],
         **win.step_gaps(t0, t1), **in_window_gc,
         hot_tier_hits=eng.stats.buffer_hits,
         hot_tier_misses=eng.stats.buffer_misses)

    prompts = {i.rid: i.prompt for i in traffic.items.values()}
    picked = sample(list(win.finished_tokens.items()),
                    cell["compare"]["requests"], args.seed)
    failed = len(eng.shed)
    del eng, win.eng
    gc.unfreeze()
    gc.collect()
    jax.clear_caches()
    t_ref = time.monotonic()
    gaps, cgaps, bad = compare(spec, sizes, wseed, prompts, picked,
                               args.control)
    t_ref = time.monotonic() - t_ref
    emit(phase="compare", reference_s=t_ref, requests=len(picked),
         tokens=sum(len(g) for g in gaps), readings=readings(gaps),
         control_readings=readings(cgaps) if args.control else None,
         gap_per_request=[float(g.max()) for g in gaps],
         control_gap_per_request=[float(g.max()) for g in cgaps])
    if args.control:
        # the control in the program's place: its tokens are in vocab
        gaps, bad = cgaps, 0
    got = readings(gaps)
    compared = {k: {"value": got[k], "limit": v}
                for k, v in cell["limits"].items()}
    compared["tokens_out_of_vocab"] = {"value": bad, "limit": 0}
    correct = bool(gaps) and all(c["value"] <= c["limit"]
                                 for c in compared.values())
    attempted = len(records)

    if args.reduced:
        result = {"rehearsal": "reduced", "correct": correct,
                  "attempted": attempted, "failed": failed,
                  "device": device, "compared": compared}
    else:
        device["memory_peak_bytes"] = mem
        if args.trace:
            run = SimpleNamespace(
                records=records, t0=tt, t1=t1, trace=trace, peaks=peak,
                prefills=win.prefills, scope_ms=scope_ms,
                step_work=_step_work(spec, sizes, win, tt, t1))
            metrics = {}
            for m in metric_entries(spec, "per_layer"):
                reader = importlib.import_module(f"chipbench.metrics.{m['name']}")
                v = reader.read(run)
                if v is not None:
                    metrics[m["name"]] = {"value": v, "unit": m["unit"]}
            device["busy_s"] = trace.busy_s()
            device["window_s"] = trace.window_s()
        else:
            e2e = stats.end_to_end(records, t0, t1)
            e2e["setup_s"] = t_setup - T_PROCESS
            metrics = {m["name"]: {"value": e2e[m["name"]], "unit": m["unit"]}
                       for m in metric_entries(spec, "end_to_end")
                       if e2e.get(m["name"]) is not None}
        result = {"correct": correct, "attempted": attempted,
                  "failed": failed, "metrics": metrics, "device": device}
        if args.trace:
            result["breakdown"] = {"device_ops": trace.top_ops(),
                                   "idle_gaps": trace.idle_by_span()}
        result["compared"] = compared
    for k, v in compared.items():
        print(f"{k} {v['value']} limit {v['limit']}", file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


def _step_work(spec, sizes, win: Window, t0, t1):
    work = importlib.import_module(f"chipbench.work.{spec.conf['work']}")
    return [work.decode_step(sizes, ctx) for t, ctx in win.steps
            if t0 < t <= t1 and ctx]


if __name__ == "__main__":
    sys.exit(main())
