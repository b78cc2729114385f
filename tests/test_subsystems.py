"""Aux subsystem tests: distributed checkpoint, hapi Model, profiler,
launcher env, jit save/load."""
import json
import os

import numpy as np
import pytest

import paddle_tpu as P
import paddle_tpu.nn as nn
from paddle_tpu.distributed import fleet, topology
from paddle_tpu.core.export_compat import jax_export_available

requires_jax_export = pytest.mark.skipif(
    not jax_export_available(),
    reason="jax.export unavailable in this jax build")


@pytest.fixture(autouse=True)
def fresh_topology():
    topology.reset_topology()
    yield
    topology.reset_topology()


def test_dist_checkpoint_roundtrip(tmp_path):
    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict,
    )

    P.seed(0)
    m = nn.Linear(8, 8)
    sd = m.state_dict()
    save_state_dict(sd, str(tmp_path / "ckpt"))
    m2 = nn.Linear(8, 8)
    sd2 = m2.state_dict()
    load_state_dict(sd2, str(tmp_path / "ckpt"))
    np.testing.assert_allclose(sd2["weight"].numpy(), sd["weight"].numpy())


def test_dist_checkpoint_reshard(tmp_path):
    """Save sharded one way, load into a differently-sharded target."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as Pt

    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict,
    )

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2, "pp_degree": 1,
                               "sep_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    topo = fleet.get_hybrid_communicate_group()
    data = np.arange(64, dtype=np.float32).reshape(8, 8)
    # saved dp-sharded on rows
    src = P.Tensor(jax.device_put(
        data, NamedSharding(topo.spmd_mesh, Pt("dp", None))))
    save_state_dict({"w": src}, str(tmp_path / "ck2"))
    # load into an mp-sharded-on-cols target
    tgt = P.Tensor(jax.device_put(
        np.zeros((8, 8), np.float32),
        NamedSharding(topo.spmd_mesh, Pt(None, "mp"))))
    load_state_dict({"w": tgt}, str(tmp_path / "ck2"))
    np.testing.assert_allclose(np.asarray(tgt._value), data)
    assert "mp" in str(tgt._value.sharding.spec)


def test_dist_checkpoint_no_full_materialization(tmp_path):
    """Loading a sharded target must assemble per-device blocks only —
    never the full global tensor on host (reference point-to-point load,
    load_state_dict.py:65)."""
    import jax
    from jax.sharding import NamedSharding, PartitionSpec as Pt

    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict,
    )
    from paddle_tpu.distributed.checkpoint.api import last_load_stats

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 2, "pp_degree": 1,
                               "sep_degree": 1, "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    topo = fleet.get_hybrid_communicate_group()
    data = np.arange(256, dtype=np.float32).reshape(16, 16)
    # saved mp-sharded on cols, dp-replicated (exercises save dedup too)
    src = P.Tensor(jax.device_put(
        data, NamedSharding(topo.spmd_mesh, Pt(None, "mp"))))
    save_state_dict({"w": src}, str(tmp_path / "ck3"))
    # target sharded over BOTH axes: blocks are 8x8 = 64 elems
    tgt = P.Tensor(jax.device_put(
        np.zeros((16, 16), np.float32),
        NamedSharding(topo.spmd_mesh, Pt("dp", "mp"))))
    load_state_dict({"w": tgt}, str(tmp_path / "ck3"))
    np.testing.assert_allclose(np.asarray(tgt._value), data)
    assert last_load_stats["full_materialized"] == []
    assert last_load_stats["max_block_elems"] <= 64, last_load_stats


def test_dist_checkpoint_bf16_bit_exact(tmp_path):
    """bfloat16 shards must round-trip bit-for-bit (no float32 detour)."""
    import jax.numpy as jnp
    import ml_dtypes

    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict,
    )

    rs = np.random.RandomState(7)
    vals = rs.randn(32, 8).astype(ml_dtypes.bfloat16)
    src = P.Tensor(jnp.asarray(vals))
    save_state_dict({"p": src}, str(tmp_path / "ckbf"))
    tgt = P.Tensor(jnp.zeros((32, 8), jnp.bfloat16))
    load_state_dict({"p": tgt}, str(tmp_path / "ckbf"))
    out = np.asarray(tgt._value)
    assert out.dtype == ml_dtypes.bfloat16
    assert np.array_equal(
        out.view(np.uint16), vals.view(np.uint16))


def test_dist_checkpoint_async_save(tmp_path):
    """async_save: snapshot is taken synchronously (mutating the state
    dict right after save must not corrupt the checkpoint), IO runs on a
    background thread, wait_async_save() is the completion barrier."""
    import jax.numpy as jnp

    from paddle_tpu.distributed.checkpoint import (
        load_state_dict, save_state_dict, wait_async_save,
    )
    from paddle_tpu.distributed.checkpoint import api as ck_api

    data = np.arange(64, dtype=np.float32).reshape(8, 8)
    src = P.Tensor(jnp.asarray(data))
    sd = {"w": src}
    save_state_dict(sd, str(tmp_path / "cka"), async_save=True)
    assert ck_api._async_save_thread is not None  # really backgrounded
    # clobber the live tensor immediately — the snapshot must be immune
    sd["w"]._value = jnp.zeros((8, 8), jnp.float32)
    wait_async_save()
    assert ck_api._async_save_thread is None
    tgt = P.Tensor(jnp.zeros((8, 8), jnp.float32))
    load_state_dict({"w": tgt}, str(tmp_path / "cka"))
    np.testing.assert_allclose(np.asarray(tgt._value), data)

    # load right after an async save (no explicit wait): load's own
    # barrier must see the finished file
    save_state_dict({"w": P.Tensor(jnp.asarray(data * 2))},
                    str(tmp_path / "ckb"), async_save=True)
    tgt2 = P.Tensor(jnp.zeros((8, 8), jnp.float32))
    load_state_dict({"w": tgt2}, str(tmp_path / "ckb"))
    np.testing.assert_allclose(np.asarray(tgt2._value), data * 2)


def test_hapi_model_fit(tmp_path):
    from paddle_tpu.hapi import Model
    from paddle_tpu.metric import Accuracy
    from paddle_tpu.vision.datasets import FakeData

    P.seed(0)
    net = nn.Sequential(nn.Flatten(), nn.Linear(48, 10))
    model = Model(net)
    model.prepare(
        optimizer=P.optimizer.Adam(parameters=net.parameters(),
                                   learning_rate=1e-2),
        loss=nn.CrossEntropyLoss(),
        metrics=Accuracy())
    data = FakeData(size=64, image_shape=(3, 4, 4), num_classes=10)
    model.fit(data, batch_size=16, epochs=1, verbose=0)
    res = model.evaluate(data, batch_size=16)
    assert "loss" in res and "acc" in res
    preds = model.predict(data, batch_size=16, stack_outputs=True)
    assert preds[0].shape == (64, 10)
    model.save(str(tmp_path / "m"))
    model.load(str(tmp_path / "m"))


def test_profiler_chrome_export(tmp_path):
    import paddle_tpu.profiler as profiler

    prof = profiler.Profiler(
        scheduler=profiler.make_scheduler(record=2),
        on_trace_ready=None, timer_only=True)
    prof.start()
    for _ in range(2):
        with profiler.RecordEvent("train_step"):
            (P.randn([32, 32]) @ P.randn([32, 32])).numpy()
        prof.step()
    prof.stop()
    path = prof.export(str(tmp_path / "trace.json"))
    with open(path) as f:
        trace = json.load(f)
    names = {e["name"] for e in trace["traceEvents"]}
    assert "train_step" in names
    agg = prof.summary()
    assert "train_step" in agg


def test_launcher_env_build():
    from paddle_tpu.distributed.launch.main import build_env, parse_args

    args = parse_args(["--nnodes", "2", "--rank", "1",
                       "--master", "10.0.0.1:8476", "train.py"])
    env = build_env(args)
    assert env["PADDLE_TRAINER_ID"] == "1"
    assert env["PADDLE_TRAINERS_NUM"] == "2"
    assert env["COORDINATOR_ADDRESS"] == "10.0.0.1:8476"


@pytest.mark.slow
def test_launcher_end_to_end(tmp_path):
    """Shell out to the REAL launcher (SURVEY §4 mechanism 2c — the
    reference's test_communication_api_base.py:59 drives
    `python -m paddle.distributed.launch` the same way): two workers on
    localhost, per-rank env wiring, per-rank log files, rc 0."""
    import subprocess
    import sys

    script = tmp_path / "worker.py"
    script.write_text(
        "import os\n"
        "print('rank', os.environ['PADDLE_TRAINER_ID'], 'of',\n"
        "      os.environ['PADDLE_TRAINERS_NUM'], flush=True)\n")
    log_dir = tmp_path / "logs"
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(log_dir), str(script)],
        env=env, cwd=repo, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "rank 0 of 2" in (log_dir / "workerlog.0").read_text()
    assert "rank 1 of 2" in (log_dir / "workerlog.1").read_text()


@pytest.mark.slow
def test_launcher_restart_recovers_and_gives_up(tmp_path):
    """--max_restart semantics end-to-end: a worker that fails once is
    relaunched and the pod exits 0; a permanently failing worker exhausts
    the budget and the launcher surfaces its exit code."""
    import subprocess
    import sys

    marker = tmp_path / "attempted"
    flaky = tmp_path / "flaky.py"
    flaky.write_text(
        f"import os, sys\n"
        f"m = {str(marker)!r}\n"
        f"if not os.path.exists(m):\n"
        f"    open(m, 'w').close()\n"
        f"    sys.exit(7)\n"
        f"print('recovered', flush=True)\n")
    env = dict(os.environ)
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restart", "2", "--log_dir", str(tmp_path / "l1"),
         str(flaky)],
        env=env, cwd=repo, capture_output=True, text=True, timeout=120)
    assert r.returncode == 0, (r.stdout, r.stderr)
    assert "restarting pod" in r.stderr

    dead = tmp_path / "dead.py"
    dead.write_text("import sys; sys.exit(9)\n")
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--max_restart", "1", "--log_dir", str(tmp_path / "l2"),
         str(dead)],
        env=env, cwd=repo, capture_output=True, text=True, timeout=120)
    assert r.returncode == 9, (r.stdout, r.stderr)
    assert "giving up" in r.stderr


def _launch_two_process(tmp_path, worker_src, timeout=420):
    """Shared 2-process launcher harness: writes the worker (sys.path
    preamble prepended), pins the ranks to the CPU, launches
    via `paddle_tpu.distributed.launch`, asserts rc == 0, and returns
    {rank: workerlog text}."""
    import subprocess
    import sys

    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    worker = tmp_path / "worker.py"
    worker.write_text(
        f"import os, sys\nsys.path.insert(0, {repo!r})\n" + worker_src)
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    env["XLA_FLAGS"] = "--xla_force_host_platform_device_count=1"
    log_dir = tmp_path / "logs"
    r = subprocess.run(
        [sys.executable, "-m", "paddle_tpu.distributed.launch",
         "--nproc_per_node", "2", "--log_dir", str(log_dir),
         str(worker)],
        env=env, cwd=repo, capture_output=True, text=True, timeout=timeout)
    logs = {i: (log_dir / f"workerlog.{i}").read_text()
            for i in range(2) if (log_dir / f"workerlog.{i}").exists()}
    assert r.returncode == 0, (r.stdout, r.stderr, logs)
    return logs


@pytest.mark.slow
def test_launcher_two_process_jax_distributed(tmp_path):
    """REAL multi-process collective through the launcher (SURVEY §2.2
    TCPStore role → jax coordination service): two ranks initialize
    jax.distributed over the launcher-provided COORDINATOR_ADDRESS, see
    a 2-device global topology, and allgather across processes."""
    logs = _launch_two_process(tmp_path, (
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from paddle_tpu.distributed.parallel import init_parallel_env\n"
        "init_parallel_env()\n"
        "assert jax.process_count() == 2, jax.process_count()\n"
        "assert jax.device_count() == 2, jax.device_count()\n"
        "from jax.experimental import multihost_utils\n"
        "rank = jax.process_index()\n"
        "got = multihost_utils.process_allgather(\n"
        "    jnp.asarray([float(rank + 1)]))\n"
        "assert got.ravel().tolist() == [1.0, 2.0], got\n"
        "print('rank', rank, 'allgather ok', flush=True)\n"))
    text = "".join(logs.values())
    assert "rank 0 allgather ok" in text and "rank 1 allgather ok" in text


def _two_process_training(tmp_path, dp, mp, sharding, per_rank_seed):
    """Two launcher-spawned processes over the jax coordination service
    form one global 2-device mesh and run the compiled hybrid train step
    (SURVEY §2.2 comm backend at scale). Returns per-rank loss strings."""
    logs = _launch_two_process(tmp_path, (
        "import numpy as np\n"
        "import jax\n"
        "import paddle_tpu as P\n"
        "from paddle_tpu.distributed import fleet, topology\n"
        "from paddle_tpu.distributed.parallel import init_parallel_env\n"
        "from paddle_tpu.models.gpt import (GPTForCausalLM,\n"
        "    GPTPretrainingCriterion, gpt_tiny)\n"
        "init_parallel_env()\n"
        "assert jax.process_count() == 2\n"
        "rank = jax.process_index()\n"
        "topology.reset_topology()\n"
        "strategy = fleet.DistributedStrategy()\n"
        f"strategy.hybrid_configs = {{'dp_degree': {dp}, "
        f"'mp_degree': {mp},\n"
        "    'pp_degree': 1, 'sep_degree': 1, "
        f"'sharding_degree': {dp if sharding else 1}}}\n"
        + ("strategy.sharding = True\n"
           "strategy.sharding_configs = {'stage': 2}\n" if sharding
           else "")
        + "fleet.init(is_collective=True, strategy=strategy)\n"
        "P.seed(0)  # same init on both ranks\n"
        "model = fleet.distributed_model(GPTForCausalLM(gpt_tiny()))\n"
        "opt = fleet.distributed_optimizer(P.optimizer.AdamW(\n"
        "    parameters=model.parameters(), learning_rate=1e-3))\n"
        "crit = GPTPretrainingCriterion()\n"
        + (f"rs = np.random.RandomState(100 + rank)\n" if per_rank_seed
           else "rs = np.random.RandomState(100)\n")
        + "ids = P.to_tensor(rs.randint(0, 1024, (2, 32)), 'int32')\n"
        "labels = P.to_tensor(rs.randint(0, 1024, (2, 32)), 'int32')\n"
        "losses = [float(model.train_batch((ids, labels), optimizer=opt,\n"
        "    loss_fn=crit)) for _ in range(3)]\n"
        "assert all(np.isfinite(l) for l in losses), losses\n"
        "assert losses[-1] < losses[0], losses\n"
        "print('rank', rank, 'losses', [round(l, 6) for l in losses],\n"
        "      flush=True)\n"))
    import re as _re

    return {i: _re.search(r"losses \[([^\]]+)\]", logs[i]).group(1)
            for i in logs}


@pytest.mark.slow
def test_two_process_data_parallel_training(tmp_path):
    """dp=2 + ZeRO-2 across processes: each rank feeds its LOCAL batch
    shard; grads all-reduce and dp-sharded optimizer slots assemble
    across processes. Losses identical on both ranks and decreasing."""
    got = _two_process_training(tmp_path, dp=2, mp=1, sharding=True,
                                per_rank_seed=True)
    assert got[0] == got[1], got


@pytest.mark.slow
def test_two_process_tensor_parallel_training(tmp_path):
    """mp=2 across processes: Column/RowParallelLinear weights are
    SHARDED over non-addressable devices (global-array assembly in
    _put_state) and activations all-reduce over ICI-analog sockets.
    Same data both ranks; losses identical and decreasing."""
    got = _two_process_training(tmp_path, dp=1, mp=2, sharding=False,
                                per_rank_seed=False)
    assert got[0] == got[1], got


@pytest.mark.slow
def test_two_process_spmd_pipeline(tmp_path):
    """pp=2 ACROSS processes: the collective (one-program) pipeline runs
    stage 0 on rank 0's device and stage 1 on rank 1's, boundary
    activations crossing processes as ppermute collectives — the thing
    the per-stage-jit tier cannot do (a process cannot jit onto devices
    it does not own). Both ranks must see the sequential oracle's values
    and gradients."""
    logs = _launch_two_process(tmp_path, (
        "import numpy as np\n"
        "import jax\n"
        "import jax.numpy as jnp\n"
        "from jax.sharding import Mesh, NamedSharding, PartitionSpec as P\n"
        "import paddle_tpu  # noqa: F401 (plugin/bootstrap parity)\n"
        "from paddle_tpu.distributed.parallel import init_parallel_env\n"
        "from paddle_tpu.distributed.pipeline_spmd import (\n"
        "    spmd_pipeline, spmd_pipeline_reference, stack_stages)\n"
        "init_parallel_env()\n"
        "assert jax.process_count() == 2\n"
        "mesh = Mesh(np.array(jax.devices()), ('pp',))\n"
        "def block(p, a):\n"
        "    h = jax.nn.gelu(a @ p['w'] + p['b'])\n"
        "    return a + h\n"
        "rs = np.random.RandomState(0)\n"
        "stages = [{'w': jnp.asarray(rs.randn(8, 8) * 0.1, jnp.float32),\n"
        "           'b': jnp.asarray(rs.randn(8) * 0.1, jnp.float32)}\n"
        "          for _ in range(2)]\n"
        "x = jnp.asarray(rs.randn(4, 2, 8), jnp.float32)\n"
        "stacked = jax.tree_util.tree_map(\n"
        "    lambda l: jax.device_put(l, NamedSharding(\n"
        "        mesh, P(*(('pp',) + (None,) * (l.ndim - 1))))),\n"
        "    stack_stages(stages))\n"
        "def loss_pp(s, x):\n"
        "    return jnp.mean(spmd_pipeline(block, s, x, mesh=mesh) ** 2)\n"
        "def loss_seq(ss, x):\n"
        "    return jnp.mean(spmd_pipeline_reference(block, ss, x) ** 2)\n"
        "lp, gp = jax.value_and_grad(loss_pp)(stacked, x)\n"
        "lw, gw = jax.value_and_grad(loss_seq)(stages, x)\n"
        "gw = stack_stages(gw)\n"
        "lp = float(jax.device_get(lp))\n"
        "np.testing.assert_allclose(lp, float(lw), rtol=2e-5)\n"
        "for k in ('w', 'b'):\n"
        "    got = np.asarray(jax.device_get(\n"
        "        jax.jit(lambda g: g, out_shardings=NamedSharding(\n"
        "            mesh, P()))(gp[k])))\n"
        "    np.testing.assert_allclose(got, np.asarray(gw[k]),\n"
        "                               rtol=2e-4, atol=2e-6)\n"
        "print('rank', jax.process_index(), 'spmd-pp parity ok',\n"
        "      flush=True)\n"))
    text = "".join(logs.values())
    assert "rank 0 spmd-pp parity ok" in text
    assert "rank 1 spmd-pp parity ok" in text


@requires_jax_export
def test_jit_save_load_roundtrip(tmp_path):
    P.seed(0)
    m = nn.Sequential(nn.Linear(6, 12), nn.ReLU(), nn.Linear(12, 3))
    m.eval()
    x = P.randn([2, 6])
    P.jit.save(m, str(tmp_path / "net"), input_spec=[x._value])
    loaded = P.jit.load(str(tmp_path / "net"))
    np.testing.assert_allclose(loaded(x).numpy(), m(x).numpy(), rtol=1e-6)


def test_amp_train_step_casts_float_inputs():
    """bf16 AMP train step with float32 image inputs: the step must cast
    floating batch leaves to the compute dtype (conv operands must agree
    — regression for the f32-input/bf16-weight conv mismatch)."""
    import jax.numpy as jnp

    from paddle_tpu.nn import Conv2D, CrossEntropyLoss, Flatten, Linear
    from paddle_tpu import nn as pnn

    class Tiny(pnn.Layer):
        def __init__(self):
            super().__init__()
            self.conv = Conv2D(3, 4, 3)
            self.flat = Flatten()
            self.fc = Linear(4 * 6 * 6, 5)

        def forward(self, x):
            return self.fc(self.flat(self.conv(x)))

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 1, "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 1,
                               "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    model = fleet.distributed_model(Tiny())
    opt = fleet.distributed_optimizer(
        P.optimizer.SGD(parameters=model.parameters(), learning_rate=1e-2))
    step = model.build_train_step(opt, CrossEntropyLoss(),
                                  amp_dtype="bfloat16")
    imgs = P.to_tensor(np.random.RandomState(0)
                       .randn(2, 3, 8, 8).astype(np.float32))
    lbl = P.to_tensor(np.array([1, 3]), "int32")
    l1 = float(np.asarray(step(imgs, lbl)._value))
    l2 = float(np.asarray(step(imgs, lbl)._value))
    assert np.isfinite(l1) and np.isfinite(l2)


@requires_jax_export
def test_inference_http_serving(tmp_path):
    """Inference serving tier (reference deployment surface role): save
    an inference model, serve it over HTTP, predict via the client."""
    from paddle_tpu import static
    from paddle_tpu.inference.serving import InferenceClient, InferenceServer

    P.enable_static()
    try:
        x = static.data("x", [-1, 4], "float32")
        lin = nn.Linear(4, 3)
        out = nn.functional.softmax(lin(x))
        exe = static.Executor()
        prefix = str(tmp_path / "served")
        static.save_inference_model(prefix, [x], [out], exe)
        xv = np.random.RandomState(0).rand(2, 4).astype(np.float32)
        (ref,) = exe.run(feed={"x": xv}, fetch_list=[out])
    finally:
        P.disable_static()

    srv = InferenceServer(prefix, port=0).start()
    try:
        client = InferenceClient(srv.address)
        h = client.health()
        assert h["status"] == "ok" and h["inputs"] == ["x"]
        outs = client.predict(x=xv)
        (got,) = outs.values()
        np.testing.assert_allclose(got, ref, rtol=1e-5, atol=1e-6)
    finally:
        srv.shutdown()


def test_hapi_fit_amp_and_accumulation(tmp_path):
    """prepare(amp_configs=...) and accumulate_grad_batches are honored
    (previously silent no-op args)."""
    from paddle_tpu.hapi import Model
    from paddle_tpu.vision.datasets import FakeData

    P.seed(0)
    net = nn.Sequential(nn.Flatten(), nn.Linear(48, 10))
    model = Model(net)
    model.prepare(
        optimizer=P.optimizer.SGD(parameters=net.parameters(),
                                  learning_rate=1e-2),
        loss=nn.CrossEntropyLoss(),
        amp_configs={"level": "O1", "dtype": "bfloat16"})
    assert model._amp_level == "O1"
    data = FakeData(size=32, image_shape=(3, 4, 4), num_classes=10)
    model.fit(data, batch_size=8, epochs=1, verbose=0,
              accumulate_grad_batches=2)
    res = model.evaluate(data, batch_size=8)
    assert np.isfinite(res["loss"])


def test_hapi_fit_data_parallel():
    """With a dp>1 topology initialized, prepare() wraps the network in
    DataParallel so fit syncs grads across dp ranks."""
    from paddle_tpu.distributed.parallel import DataParallel
    from paddle_tpu.hapi import Model
    from paddle_tpu.vision.datasets import FakeData

    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 2, "mp_degree": 1,
                               "pp_degree": 1, "sep_degree": 1,
                               "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    P.seed(0)
    net = nn.Sequential(nn.Flatten(), nn.Linear(48, 10))
    model = Model(net)
    model.prepare(
        optimizer=P.optimizer.SGD(parameters=net.parameters(),
                                  learning_rate=1e-2),
        loss=nn.CrossEntropyLoss())
    assert isinstance(model.network, DataParallel)
    data = FakeData(size=16, image_shape=(3, 4, 4), num_classes=10)
    model.fit(data, batch_size=8, epochs=1, verbose=0)


def test_reduce_lr_on_plateau_callback():
    from paddle_tpu.hapi.callbacks import ReduceLROnPlateau

    class _Opt:
        def __init__(self):
            self._lr = 0.1

        def get_lr(self):
            return self._lr

        def set_lr(self, v):
            self._lr = v

    class _Model:
        pass

    cb = ReduceLROnPlateau(monitor="loss", factor=0.5, patience=2,
                           verbose=0, min_lr=0.01)
    m = _Model(); m._optimizer = _Opt()
    cb.model = m
    cb.on_eval_end({"loss": 1.0})
    for _ in range(2):  # no improvement x2 -> reduce
        cb.on_eval_end({"loss": 1.0})
    assert abs(m._optimizer.get_lr() - 0.05) < 1e-9
    cb.on_eval_end({"loss": 0.5})   # improvement resets
    assert abs(m._optimizer.get_lr() - 0.05) < 1e-9
    import pytest

    with pytest.raises(ValueError):
        ReduceLROnPlateau(factor=1.5)
    from paddle_tpu.hapi.callbacks import WandbCallback

    with pytest.raises(ImportError, match="wandb"):
        WandbCallback()


def _resume_run(topo_cfg, batches, n_steps, ckpt=None, save_at=None,
                save_path=None):
    """Build a fresh GPT-tiny hybrid step under `topo_cfg`, optionally
    load a training checkpoint, run `n_steps`, optionally save. Uses a
    DECAYING LR schedule so a resume that restarts the scheduler (while
    the Adam step counter continues) shows up as diverging losses.
    Returns the per-step losses."""
    from paddle_tpu.models.gpt import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_tiny,
    )

    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    cfg = dict({"pp_degree": 1, "sep_degree": 1, "sharding_degree": 1},
               **topo_cfg)
    strategy.hybrid_configs = cfg
    if cfg["sharding_degree"] > 1:
        strategy.sharding = True
        strategy.sharding_configs = {"stage": 2}
    fleet.init(is_collective=True, strategy=strategy)
    P.seed(0)
    model = fleet.distributed_model(
        GPTForCausalLM(gpt_tiny(dropout=0.0)))
    sched = P.optimizer.lr.StepDecay(learning_rate=1e-3, step_size=2,
                                     gamma=0.5)
    opt = fleet.distributed_optimizer(P.optimizer.AdamW(
        parameters=model.parameters(), learning_rate=sched))
    step = model.build_train_step(opt, GPTPretrainingCriterion())
    if ckpt is not None:
        step.load_train_state(ckpt)
    losses = []
    for i in range(n_steps):
        ids, labels = batches[i]
        losses.append(float(step(P.to_tensor(ids, "int32"),
                                 P.to_tensor(labels, "int32"))))
        sched.step()
        if save_at is not None and i + 1 == save_at:
            step.save_train_state(save_path)
    return losses


@pytest.mark.slow
def test_train_resume_exact_and_across_topologies(tmp_path):
    """Exact training resume (VERDICT aux: checkpoint/resume at depth):
    params + every AdamW slot + the step counter (bias correction!)
    round-trip through the distributed checkpoint.

    Same topology: the resumed run's losses must match the uninterrupted
    run's almost bitwise. Different topology (dp4·mp2 -> dp2·mp4): the
    checkpoint reshards leaf-by-leaf on load; losses match to reduction-
    order tolerance."""
    rs = np.random.RandomState(0)
    batches = [(rs.randint(0, 1024, (4, 32)), rs.randint(0, 1024, (4, 32)))
               for _ in range(6)]
    a = _resume_run({"dp_degree": 4, "mp_degree": 2}, batches, 6)
    ck = str(tmp_path / "resume_ck")
    b_head = _resume_run({"dp_degree": 4, "mp_degree": 2}, batches, 3,
                         save_at=3, save_path=ck)
    np.testing.assert_allclose(b_head, a[:3], rtol=1e-6)
    # same-topology resume: steps 4-6 continue as if never interrupted
    b_tail = _resume_run({"dp_degree": 4, "mp_degree": 2}, batches[3:], 3,
                         ckpt=ck)
    np.testing.assert_allclose(b_tail, a[3:], rtol=1e-5)
    # cross-topology resume: the same checkpoint restores into a
    # dp2·mp4 step (params AND slots resharded); only reduction order
    # may differ
    c_tail = _resume_run({"dp_degree": 2, "mp_degree": 4}, batches[3:], 3,
                         ckpt=ck)
    np.testing.assert_allclose(c_tail, a[3:], rtol=5e-4)
    # ZeRO-2 slots: dp4-sharded moments reshard into a dp2-sharded step
    z = _resume_run({"dp_degree": 4, "mp_degree": 2,
                     "sharding_degree": 4}, batches, 3,
                    save_at=3, save_path=str(tmp_path / "z_ck"))
    np.testing.assert_allclose(z, a[:3], rtol=1e-5)
    z_tail = _resume_run({"dp_degree": 2, "mp_degree": 4,
                          "sharding_degree": 2}, batches[3:], 3,
                         ckpt=str(tmp_path / "z_ck"))
    np.testing.assert_allclose(z_tail, a[3:], rtol=5e-4)
    # strictness: a different model's checkpoint refuses to partially
    # resume (missing leaves raise instead of silently mixing loaded
    # and fresh state)
    from paddle_tpu.models.gpt import (
        GPTForCausalLM, GPTPretrainingCriterion, gpt_tiny,
    )

    topology.reset_topology()
    strategy = fleet.DistributedStrategy()
    strategy.hybrid_configs = {"dp_degree": 4, "mp_degree": 2,
                               "pp_degree": 1, "sep_degree": 1,
                               "sharding_degree": 1}
    fleet.init(is_collective=True, strategy=strategy)
    P.seed(0)
    other = fleet.distributed_model(GPTForCausalLM(
        gpt_tiny(dropout=0.0, num_layers=3)))
    oopt = fleet.distributed_optimizer(P.optimizer.AdamW(
        parameters=other.parameters(), learning_rate=1e-3))
    ostep = other.build_train_step(oopt, GPTPretrainingCriterion())
    with pytest.raises(ValueError, match="missing"):
        ostep.load_train_state(ck)
