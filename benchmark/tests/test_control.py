"""The control, at a size a test run can hold: the reference put in the
program's place and computed one precision below the stated one must come out
NOT correct under the cells' own limits.  (What it reads at the cells' own
sizes on the chip is in PERF.md section 2.)"""
import pytest

from harness import check, common, traffic, weights

MID = dict(vocab_size=4096, hidden_size=512, num_hidden_layers=2,
           num_attention_heads=4, head_dim=128, intermediate_size=2048,
           max_position_embeddings=512)
CELLS = ["gpt3-125m.train.seq1024", "gpt3-125m.train.seq2048"]


@pytest.mark.parametrize("cell_name", CELLS)
@pytest.mark.parametrize("seed", [1, 2, 3])
def test_train_control_fp8_fails_a_number(seed, cell_name):
    """bfloat16 autocast is the stated precision; float8 (e4m3) matmul
    operands are the step below."""
    ref = common.load_module("reference", "gpt")
    cell = common.load_json("workloads", cell_name + ".json")
    cfg = dict(common.load_json("configs", cell["config"] + ".json"), **MID)
    job = {"global_batch": 8, "sequence_length": 128}
    p0 = weights.make(cfg, seed, "float32")
    batches = [traffic.train_batch(job, cfg["vocab_size"], seed, i) for i in range(3)]
    opt = cfg["training"]["optimizer"]
    follow = lambda **kw: ref.train_readings(cfg, opt, p0, batches, 4,
                                             leaves=weights.logical_leaves, **kw)
    sound = follow()
    numbers, _ = check.train_numbers(follow(quant=ref.fp8_fake_quant), sound)
    ok, failing = check.verdict(check.with_limits(numbers, cell["limits"], True))
    assert not ok and failing, numbers
    # and the reference against itself passes the same limits
    same, _ = check.train_numbers(sound, sound)
    assert check.verdict(check.with_limits(same, cell["limits"], True)) == (True, [])
