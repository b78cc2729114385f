"""chip_smoke.py on the CPU: the tiny rehearsal passes both phases, and
without --rehearse there is no CPU path at all."""
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import chip_smoke  # noqa: E402


def test_rehearsal_runs_both_phases(capsys, monkeypatch):
    from paddle_tpu import backend_guard, observability as obs
    from paddle_tpu.distributed import topology

    # the test session keeps its own compile cache (tests/conftest.py)
    monkeypatch.setattr(backend_guard, "enable_compile_cache",
                        lambda *a, **k: None)
    try:
        assert chip_smoke.main(["--rehearse"]) == 0
    finally:
        obs.detach()
        topology.reset_topology()
    lines = [json.loads(l) for l in capsys.readouterr().out.splitlines()
             if l.startswith("{")]
    phases = {l["phase"]: l for l in lines if "phase" in l}
    assert set(phases) == {"train", "serve"}
    losses = phases["train"]["losses"]
    assert len(losses) == 5 and losses[-1] < losses[0]
    assert phases["serve"]["requests"] == 4
    assert phases["serve"]["common_prefix_with_generate"] >= 1
    # a rehearsal never prints the success line
    assert "ok" not in lines[-1] and lines[-1]["rehearsed"] is True


def test_no_cpu_path_without_rehearse(capsys, monkeypatch):
    """JAX_PLATFORMS=cpu (conftest): non-zero before any model is built,
    and the last line is not the success object."""
    import paddle_tpu.models.gpt as gpt

    def boom(*a, **k):
        raise AssertionError("built a model without a TPU")

    monkeypatch.setattr(gpt, "GPTForCausalLM", boom)
    assert chip_smoke.main([]) != 0
    out = capsys.readouterr()
    assert '"ok"' not in out.out
    assert "needs a TPU" in out.err
