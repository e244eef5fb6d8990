"""CPU rehearsal of every cell at a tiny configuration, with the timed path
sound and then broken underneath.

Each cell runs in one child process (it holds JAX's global state): first
the sound run, then the control (the reference in fp8 judged in the
program's place), then one run for each fault a one-chip serving cell can
have.  The harness's look for a chip is skipped by ``--reduced``;
everything else is the run as measured: warm-up, the window driving
``Engine.step``, the sample of finished requests and the comparison with
the plain reference.  A sound run must come out correct and every fault,
and the control, must not.  (The exchange between chips is not a fault
these one-chip cells can have.)
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

ROOT = Path(__file__).resolve().parents[2]
CELLS = [w["name"] for w in
         json.loads((ROOT / "BENCHMARK.json").read_text())["workloads"]]

DRIVER = r"""
import json, sys
import jax.numpy as jnp
from chipbench import run

build = run.build_engine

def broken(fault):
    def build_broken(cfg, cell, seed):
        eng = build(cfg, cell, seed)
        decode = eng._decode
        def step(params, state, tokens, *a):
            new_state, logits = decode(params, state, tokens, *a)
            if fault == "state_unchanged":
                return state, logits
            half = logits.shape[0] // 2
            if fault == "half_batch":
                return new_state, logits.at[half:].set(logits[:half])
            if fault == "token_altered":
                return new_state, jnp.roll(logits, 1, axis=-1)
            raise ValueError(fault)
        eng._decode = step
        return eng
    return build_broken

cell = sys.argv[1]
FAULTS = sys.argv[2:]
base = ["--workload", cell, "--seed", "3000000019", "--seconds", "2",
        "--trace", "0", "--reduced"]
for fault in FAULTS:
    sound = fault in ("sound", "control")
    run.build_engine = build if sound else broken(fault)
    print("FAULT", fault, flush=True)
    run.main(base + (["--control"] if fault == "control" else []))
"""


FAULTS = ["sound", "control", "state_unchanged", "half_batch",
          "token_altered"]


def results(out: str):
    fault, got = None, {}
    for line in out.splitlines():
        if line.startswith("FAULT "):
            fault = line.split()[1]
        elif line.startswith("{") and fault is not None:
            d = json.loads(line)
            if d.get("phase") == "compare":
                got.setdefault(fault, {})["compare"] = d
            elif "correct" in d:
                got.setdefault(fault, {})["result"] = d
    return got


@pytest.mark.parametrize("cell", CELLS)
def test_rehearsal_sound_and_broken(cell):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run([sys.executable, "-c", DRIVER, cell, *FAULTS],
                       cwd=ROOT,
                       env=env, capture_output=True, text=True, timeout=600)
    assert p.returncode == 0, p.stderr[-3000:]
    got = results(p.stdout)
    assert set(got) == set(FAULTS), p.stdout[-3000:]
    sound = got["sound"]["result"]
    assert sound["correct"], sound
    assert sound["rehearsal"] == "reduced" and "metrics" not in sound
    assert sound["attempted"] > 0 and sound["failed"] == 0
    # the control reads the program's tokens too, and judges its own
    control = got["control"]
    assert control["compare"]["readings"] == got["sound"]["compare"][
        "readings"]
    assert not control["result"]["correct"], control
    for fault in FAULTS[2:]:
        res = got[fault]["result"]
        assert not res["correct"], (fault, res)
