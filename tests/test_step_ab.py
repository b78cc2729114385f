"""The layout A/B harness itself runs in tier-1 (--smoke CPU mode), so
it is known to work before it is given chip time."""
import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
STEP_AB = os.path.join(REPO, "tools", "step_ab.py")


def _run(*argv, timeout=420):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    env.pop("FLAGS_flash_layout", None)
    return subprocess.run([sys.executable, STEP_AB, *argv],
                         capture_output=True, text=True, cwd=REPO,
                         timeout=timeout, env=env)


def _rows(stdout):
    out = []
    for line in stdout.splitlines():
        line = line.strip()
        if line.startswith("{"):
            out.append(json.loads(line))
    return out


def test_step_ab_gpt_smoke_emits_ab_line_and_gate_row():
    """CPU smoke of the gpt train A/B point: the human-readable
    "AB layout=..." line AND a perf_gate-compatible row (degraded off
    accelerator, so it can never gate a CPU number against an on-chip
    floor) both come out."""
    p = _run("flat", "--smoke", "--iters", "1")
    assert p.returncode == 0, p.stdout + p.stderr
    ab = [l for l in p.stdout.splitlines() if l.startswith("AB ")]
    assert ab and "layout=flat" in ab[0] and "tokens/s=" in ab[0], \
        p.stdout
    rows = _rows(p.stdout)
    assert rows, p.stdout
    r = rows[0]
    assert r["metric"] == "step_ab_gpt_flat_train_tokens_per_sec"
    assert r["unit"] == "tokens/s" and r["value"] > 0
    assert r.get("degraded") is True


@pytest.mark.slow
def test_step_ab_swin_smoke():
    """Vision variant axis: fused vs fallback — the swin smoke point
    emits an images/s gate row."""
    p = _run("fallback", "--model", "swin", "--smoke", "--iters", "1")
    assert p.returncode == 0, p.stdout + p.stderr
    rows = _rows(p.stdout)
    assert rows and rows[0]["metric"] == \
        "step_ab_swin_fallback_train_images_per_sec"
    assert rows[0]["unit"] == "images/s" and rows[0]["value"] > 0


@pytest.mark.slow
def test_step_ab_decode_point():
    p = _run("transpose", "--smoke", "--iters", "1", "--decode")
    assert p.returncode == 0, p.stdout + p.stderr
    metrics = [r["metric"] for r in _rows(p.stdout)]
    assert "step_ab_gpt_transpose_train_tokens_per_sec" in metrics
    assert "step_ab_gpt_transpose_decode_tokens_per_sec" in metrics


def test_step_ab_rejects_bad_vision_variant():
    p = _run("flat", "--model", "swin", "--smoke")
    assert p.returncode == 1
    assert "fused|fallback" in p.stderr
