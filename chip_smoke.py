"""Bring-up smoke on one TPU: Qwen2-1.5B served at its published width.

    python chip_smoke.py                              # on a host with a TPU
    JAX_PLATFORMS=cpu python chip_smoke.py --reduced  # CPU rehearsal, tiny config

Every phase runs in this one process, since a chip belongs to one process:

1. device check: the first device must be a TPU (only ``--reduced`` accepts
   the CPU);
2. engine: ``repro.launch.serve`` serves 8 requests (7168 prompt tokens, 64
   output tokens each) on 4 slots of 8192 context with the fetch pipeline
   on; the prompt exceeds both the top-k (2048) and the hot tier (6144
   entries), and the 8 requests refill freed slots.  Token counts, token
   range and hot-tier counters are checked;
3. witness: the same parameters, initialized on the CPU and copied to the
   chip, run one prefill of a short prompt and a few decode steps on each
   backend, and the logits of every step must agree to ``LOGIT_RTOL``.

The lines before the last report, per phase, the backend compile seconds of
each jitted function (its first call; a persistent-cache hit compiles
nothing and is counted in ``persistent_cache_hits``), wall times on the
host clock and the engine's counts (``chipbench/`` measures the decode
step inside the serving loop).  The engine's
modelled latencies are not printed: they are not measurements.  No phase catches an exception: any failure exits non-zero
before the last line, which is ``{"ok": true, "device": {...}}`` only for the
full run on a TPU.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import sys
import time
from pathlib import Path

# the witness compares against the CPU backend: keep it loaded when the
# platforms are pinned (the first device still has to be the TPU)
if os.environ.get("JAX_PLATFORMS") and \
        "cpu" not in os.environ["JAX_PLATFORMS"].split(","):
    os.environ["JAX_PLATFORMS"] += ",cpu"

import jax  # noqa: E402
import numpy as np  # noqa: E402

sys.path.insert(0, str(Path(__file__).resolve().parent / "src"))

from repro.configs import get_config  # noqa: E402
from repro.launch import serve  # noqa: E402
from repro.models.model import build_model  # noqa: E402

# The two backends round bf16 weights and activations (8 significant bits)
# at different points and accumulate matmuls in a different order; the
# TPU's default matmul precision also takes f32 operands as bf16.  Over 28
# layers that drifts the logits by a few percent, while a wrong result
# (bad layout, wrong mask, garbage memory) is off by order one.  Bound: the
# relative L2 error of each step's logits vector.
LOGIT_RTOL = 5e-2
WITNESS_STEPS = 4
REQUESTS, OUT_LEN = 8, 64
SIZES = {
    # (serve.py arguments, witness prompt tokens)
    "full": (["--arch", "qwen2-1.5b", "--slots", "4", "--max-ctx", "8192",
              "--ctx", "7168"], 256),
    "reduced": (["--arch", "qwen2-1.5b", "--reduced", "--slots", "4",
                 "--max-ctx", "160", "--ctx", "48"], 32),
}


def check(cond: bool, what: str):
    if not cond:
        raise RuntimeError(f"chip_smoke check failed: {what}")


def emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileLog:
    """Compile seconds per jitted function, from JAX's monitoring events."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    FRONT = ("/jax/core/compile/jaxpr_trace_duration",
             "/jax/core/compile/jaxpr_to_mlir_module_duration")

    def __init__(self):
        self.backend_s = collections.Counter()
        self.trace_lower_s = 0.0
        self.cache_hits = 0
        jax.monitoring.register_event_duration_secs_listener(self._duration)
        jax.monitoring.register_event_listener(self._event)

    def _duration(self, event, secs, **kw):
        if event == self.BACKEND:
            self.backend_s[kw.get("fun_name", "?")] += secs
        elif event in self.FRONT:
            self.trace_lower_s += secs

    def _event(self, event, **kw):
        if event == "/jax/compilation_cache/cache_hits":
            self.cache_hits += 1

    def take(self) -> dict:
        """This phase's compile report; resets the counts."""
        big = {k: v for k, v in self.backend_s.most_common() if v >= 0.1}
        rest = [v for k, v in self.backend_s.items() if k not in big]
        out = {"backend_compile_s": big,
               "backend_compile_other_s": sum(rest),
               "backend_compile_other_n": len(rest),
               "backend_compile_total_s": sum(self.backend_s.values()),
               "trace_lower_s": self.trace_lower_s,
               "persistent_cache_hits": self.cache_hits}
        self.backend_s.clear()
        self.trace_lower_s = 0.0
        self.cache_hits = 0
        return out


def witness(cfg, prompt_len: int, chip, seed: int, log):
    """Chip logits against CPU logits for the same parameters and tokens.
    Reports before it checks, so a failed check still shows the errors."""
    t0 = time.perf_counter()
    cpu = jax.devices("cpu")[0]
    model = build_model(cfg, mode="sac")
    with jax.default_device(cpu):
        params_cpu = model.init(jax.random.PRNGKey(seed))
    params_chip = jax.device_put(params_cpu, chip)
    toks = np.random.default_rng(seed).integers(
        0, cfg.vocab, prompt_len + WITNESS_STEPS, dtype=np.int32)
    prefill, decode = jax.jit(model.prefill), jax.jit(model.decode)
    logits = {}
    for name, dev, params in (("cpu", cpu, params_cpu),
                              ("chip", chip, params_chip)):
        # the prompt is padded with the decode steps' tokens so the pools
        # have room for them; positions past ``lengths`` are masked until
        # each decode step overwrites its own
        state, last = prefill(
            params, jax.device_put(toks[None, :], dev),
            jax.device_put(np.array([prompt_len], np.int32), dev))
        steps = [last]
        for t in range(prompt_len, prompt_len + WITNESS_STEPS):
            state, last = decode(params, state,
                                 jax.device_put(toks[t:t + 1], dev))
            steps.append(last)
        logits[name] = [np.asarray(x, np.float32)[0] for x in steps]
    rel = [float(np.linalg.norm(c - g) / np.linalg.norm(g))
           for c, g in zip(logits["chip"], logits["cpu"])]
    agree = [int(c.argmax() == g.argmax())
             for c, g in zip(logits["chip"], logits["cpu"])]
    emit("witness", wall_s=time.perf_counter() - t0,
         prompt_tokens=prompt_len, decode_steps=WITNESS_STEPS,
         logit_rel_l2=rel, logit_rel_l2_limit=LOGIT_RTOL,
         argmax_agree=agree, **log.take())
    for c in logits["chip"]:
        check(bool(np.isfinite(c).all()), "chip logits are finite")
        check(c.shape == (cfg.vocab,), f"logits shape {c.shape}")
    check(max(rel) <= LOGIT_RTOL,
          f"chip vs CPU logits relative L2 error {max(rel)} > {LOGIT_RTOL}")


def engine_run(argv: list, vocab: int):
    """Serve through repro.launch.serve and check what came out."""
    t0 = time.perf_counter()
    out, eng, reqs = serve.run(argv)
    wall = time.perf_counter() - t0
    check(out["n_done"] == REQUESTS, f"n_done {out['n_done']}")
    check(out["engine_tokens"] == REQUESTS * OUT_LEN,
          f"engine_tokens {out['engine_tokens']}")
    toks = np.concatenate([np.asarray(r.out_tokens) for r in reqs])
    check(toks.size == REQUESTS * OUT_LEN, f"{toks.size} decoded tokens")
    check(bool(((toks >= 0) & (toks < vocab)).all()),
          "decoded tokens in [0, vocab)")
    check(out["buffer_hits"] + out["buffer_misses"] > 0,
          "hot tier saw reads")
    counts = {k: out[k] for k in (
        "n_done", "engine_steps", "engine_tokens", "buffer_hits",
        "buffer_misses", "buffer_hit_rate", "prefetched_entries",
        "prefetch_useful", "prefetch_wasted")}
    return eng, counts, wall


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--reduced", action="store_true",
                    help="tiny config; runs on the CPU and never reports ok")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    dev = jax.devices()[0]
    if dev.platform != "tpu" and not args.reduced:
        sys.exit(f"chip_smoke: the first device is {dev.platform!r}, not a "
                 "TPU; use --reduced for the CPU rehearsal")
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    emit("device", **device)

    serve.use_compile_cache()
    log = CompileLog()

    serve_argv, prompt_len = SIZES["reduced" if args.reduced else "full"]
    serve_argv = serve_argv + ["--out-len", str(OUT_LEN), "--requests",
                               str(REQUESTS), "--prefetch", "--seed",
                               str(args.seed)]
    cfg = get_config("qwen2-1.5b")
    if args.reduced:
        cfg = cfg.reduced()

    eng, counts, wall = engine_run(serve_argv, cfg.vocab)
    emit("engine", argv=serve_argv, wall_s=wall, **counts, **log.take())

    del eng

    witness(cfg, prompt_len, dev, args.seed, log)

    if args.reduced:
        print(json.dumps({"rehearsal": "reduced", "device": device}))
    else:
        print(json.dumps({"ok": True, "device": device}))


if __name__ == "__main__":
    main()
