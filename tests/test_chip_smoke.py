"""chip_smoke.py refuses to report success where it must not.

The script's passing run needs a TPU; these tests cover its failing
side on the CPU: no accelerator, a script copied out of the checkout, a
failed or short request, and a logit comparison broken on purpose.
"""

import importlib.util
import json
import os
import shutil
import subprocess
import sys
from pathlib import Path

import numpy as np
import pytest

from repro.serving.pipeline_server import ServeTicket

ROOT = Path(__file__).resolve().parents[1]
SCRIPT = ROOT / "chip_smoke.py"


@pytest.fixture(scope="module")
def smoke():
    spec = importlib.util.spec_from_file_location("chip_smoke", SCRIPT)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def _ok_lines(stdout: str):
    out = []
    for line in stdout.splitlines():
        try:
            obj = json.loads(line)
        except ValueError:
            continue
        if isinstance(obj, dict) and obj.get("ok"):
            out.append(line)
    return out


@pytest.mark.parametrize("where", ["checkout", "alone"])
def test_chip_smoke_fails_without_tpu(where, tmp_path):
    script = SCRIPT
    if where == "alone":
        script = tmp_path / SCRIPT.name
        shutil.copy(SCRIPT, script)
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("PYTHONPATH", None)  # the script finds src/ itself, or nothing
    proc = subprocess.run([sys.executable, str(script)], cwd=script.parent,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert _ok_lines(proc.stdout) == []


def _ticket(rid, *, tokens=None, error=None):
    tk = ServeTicket(rid=rid, doc={"id": f"r{rid}"}, submitted_at=0.0)
    tk.error = error
    if tokens is not None:
        tk.docs = [{"errors": [{"tag": "gen",
                                "value": " ".join(map(str, tokens))}]}]
    return tk


def test_check_tickets_flags_failed_and_short_requests(smoke):
    good = _ticket(1, tokens=[5, 6, 7, 8])
    n, problems = smoke.check_tickets([good], max_new=4, vocab=10)
    assert (n, problems) == (4, [])
    cases = {
        "error": _ticket(2, error=RuntimeError("device lost")),
        "short": _ticket(3, tokens=[1, 2]),
        "vocab": _ticket(4, tokens=[1, 2, 3, 10]),
        "no output": _ticket(5),
    }
    for what, tk in cases.items():
        _, problems = smoke.check_tickets([good, tk], max_new=4, vocab=10)
        assert len(problems) == 1 and problems[0].startswith(
            f"request {tk.rid}"), what


def test_compare_logits_flags_broken_check(smoke):
    rng = np.random.default_rng(0)
    ref = rng.standard_normal(4096).astype(np.float32)
    ref[7] = ref.max() + 1.0  # a clear winner: argmax must agree
    noise = 0.005 * rng.standard_normal(4096)
    stats, problems = smoke.compare_logits(ref + noise, ref)
    assert problems == [] and stats["rel_l2"] < smoke.LOGIT_RTOL
    broken = {
        "offset": ref + 4 * smoke.LOGIT_ATOL * np.sign(ref),
        "spike": np.where(np.arange(4096) == 3, ref + 1.0, ref),
        "argmax": np.where(np.arange(4096) == 7, ref - 2.0, ref),
        "nan": np.where(np.arange(4096) == 0, np.nan, ref),
    }
    for what, got in broken.items():
        _, problems = smoke.compare_logits(got, ref)
        assert problems, what
