"""step_bytes — a cell's WHOLE train step compiled for a described v5e
chip, on a machine that has none, and the program ledger's account of its
bytes.

What a kept value, a larger batch or another recomputation policy costs in
device memory is decided by the compiler, and the compiler for the chip is
installed where the chip is not.  This builds the cell's step as its
benchmark driver's `build` does — same model, same optimizer, same
`build_train_step` options — over `HybridTopology(dp=1, devices=[a described
v5e chip])`, with no weights loaded and no state placed (a described device
holds no array: the state's SHAPES come from `functional_state()` and
`jax.eval_shape(optimizer.init_state, ...)`), lowers and compiles it under
`flash_attention.force_tpu_lowering()` with telemetry on, and prints what
`observability.xla_cost.program_ledger("train_step")` then holds:

    bytes {...}    in GB, as a traced benchmark run prints it: `memory`, the
                   compiler's own totals (`memory_analysis()`); the liveness
                   sweep of the scheduled program: the peak of the
                   temporaries and where it lies, what is live there by
                   scope, what the forward holds for the backward by scope,
                   and the sweep's gap to the compiler's `temp_bytes`
    calls {...}    the Mosaic (Pallas) calls in the compiled text, by name

    JAX_PLATFORMS=cpu python tools/step_bytes.py --workload keye-vl2-ep8.train.seq8192

Nothing runs, so it gives no time; ≈ 1–2 min a cell, a few GB of host memory
(the float32 model is built on the CPU).  Block sizes the chip's autotune
search would pick are the static defaults here.  Exits 0 with a message
where no TPU topology can be described.  Outside the benchmark: it judges
nothing, it prints.
"""
from __future__ import annotations

import argparse
import collections
import json
import os
import re
import sys
from unittest import mock

os.environ.setdefault("TPU_LOG_DIR", "disabled")

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def _on_path():
    """This checkout's program and its benchmark's harness, importable."""
    for path in (ROOT, os.path.join(ROOT, "benchmark")):
        if path not in sys.path:
            sys.path.insert(0, path)


def describe_chip():
    """One device of a described `v5e:2x2`, or (None, why not)."""
    from jax.experimental import topologies

    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:      # no TPU compiler here: say so, exit 0
        return None, e
    return topo.devices[0], None


def compile_step(workload, device, layers=None):
    """The cell's train step compiled for `device` through the program
    ledger (`InstrumentedJit.aot_compile`); returns the `Compiled`.
    `layers` cuts the model to its first layers (the tests' quick form)."""
    import jax
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec

    _on_path()
    from harness import common, weights

    from paddle_tpu.distributed.train_step import DistributedTrainStep
    from paddle_tpu.observability import metrics
    from paddle_tpu.ops.pallas import flash_attention

    cell, config, _ = common.load_cell(workload)
    if layers is not None:
        config = {**config, "num_hidden_layers": layers}
    driver = common.load_module("drivers", cell["driver"])
    ctx = {"cell": cell, "config": config, "seed": 1, "devices": [device],
           "rehearse": False, "plant": None}
    # the driver's own `build`, but nothing is placed on the described
    # device: no weights from the seed, no optimizer state
    nothing = mock.MagicMock(return_value=None)
    with mock.patch.multiple(DistributedTrainStep, init_state=nothing,
                             sync_to_model=nothing), \
            mock.patch.multiple(driver if hasattr(driver, "make") else weights,
                                make=nothing, load_into=nothing):
        step, _ = driver.build(ctx)
    params, buffers = step.model.functional_state()
    opt_state = jax.eval_shape(step.optimizer.init_state, params)
    step._p_spec, step._s_spec = step._plan(params, opt_state["slots"])
    whole = NamedSharding(step.topo.spmd_mesh, PartitionSpec())

    def shape_of(x):
        return jax.ShapeDtypeStruct(jnp.shape(x), x.dtype, sharding=whole)

    job = cell["job"]
    ids = jax.ShapeDtypeStruct((job["global_batch"], job["sequence_length"]),
                               jnp.int32, sharding=whole)
    state = jax.tree_util.tree_map(
        shape_of, (params, opt_state, buffers, jax.random.PRNGKey(0)))
    lr = jax.ShapeDtypeStruct((), jnp.float32, sharding=whole)
    program = step._ensure_compiled(jax.tree_util.tree_structure((ids, ids)))
    metrics.enable()
    # a compile for a described chip is written to the persistent cache
    # but cannot be read back without one: keep it out
    jax.config.update("jax_enable_compilation_cache", False)
    with flash_attention.force_tpu_lowering():
        return program.aot_compile(*state, lr, ids, ids)


def mosaic_calls(compiled_text):
    """{kernel name: calls} of the Mosaic (Pallas) custom calls, as XLA
    names them after their kernels."""
    calls = collections.Counter(
        re.sub(r"[.\d]+$", "", name) for name in re.findall(
            r"%?([\w.\-]+) = [^\n]*custom_call_target=\"tpu_custom_call\"",
            compiled_text))
    return dict(sorted(calls.items()))


def main(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True,
                    help="a cell of BENCHMARK.json")
    a = ap.parse_args(argv)
    _on_path()
    device, why = describe_chip()
    if device is None:
        print(f"step_bytes: no v5e:2x2 topology can be described here "
              f"({type(why).__name__}: {why}); nothing compiled")
        return 0
    from paddle_tpu.observability import xla_cost

    compiled = compile_step(a.workload, device)
    entry = xla_cost.program_ledger("train_step")
    print("compile", json.dumps({
        "workload": a.workload, "device_kind": device.device_kind,
        **{k[:-2] + "s": round(entry[k] / 1e3, 2) for k in
           ("trace_ms", "lower_ms", "compile_ms", "ledger_ms")}}))
    from harness import common

    # the benchmark's own `bytes {...}` line of a traced chip run, minus
    # what only a run can say (what the runtime reserved)
    account = common.load_module("readers", "program_bytes").account
    print("bytes", json.dumps(account(entry["memory"], entry["bytes"])))
    print("calls", json.dumps(mosaic_calls(compiled.as_text())))
    return 0


if __name__ == "__main__":
    sys.exit(main())
