"""The flash block search, cold and repeated, at one attention shape.

Usage (on the chip; exits 1 without a TPU):
    python tools/flash_block_search.py B S H D [--layout flat|transpose]
                                       [--repeat 3]

Each repeat empties the tool's own autotune cache (a temporary file —
never the deployment's) and runs `flash_attention._tuned_blocks` as the
dispatch does on a cold cache.  One JSON line per search:
    {"shape": [B,S,H,D], "layout": ..., "winner": [bq, bk],
     "ms": {"(bq, bk)": fwd+bwd ms, ...}}
A search whose winner changes from repeat to repeat cannot be trusted
to pick the blocks a benchmark cell runs with (PERF.md, PR 27).
"""
import argparse
import json
import os
import sys
import tempfile

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def main(argv=None):
    p = argparse.ArgumentParser(prog="flash_block_search")
    p.add_argument("shape", nargs=4, type=int, metavar=("B", "S", "H", "D"))
    p.add_argument("--layout", default="transpose",
                   choices=("flat", "transpose"))
    p.add_argument("--repeat", type=int, default=3)
    args = p.parse_args(argv)

    cache = os.path.join(tempfile.mkdtemp(), "autotune.json")
    os.environ["PADDLE_TPU_AUTOTUNE_CACHE"] = cache
    import jax
    import jax.numpy as jnp

    from paddle_tpu.ops.pallas import autotune
    from paddle_tpu.ops.pallas import flash_attention as fa

    if jax.devices()[0].platform != "tpu":
        print("flash_block_search: no TPU", file=sys.stderr)
        return 1
    b, s, h, d = args.shape
    for _ in range(args.repeat):
        autotune.clear_cache()
        winner = fa._tuned_blocks(b, s, s, h, d, jnp.bfloat16, True,
                                  layout="flat" if args.layout == "flat"
                                  else None)
        with open(cache) as f:
            (entry,) = json.load(f).values()
        print(json.dumps({"shape": args.shape, "layout": args.layout,
                          "winner": list(winner), "ms": entry["ms"]}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
