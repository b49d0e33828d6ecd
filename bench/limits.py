"""Readings that the correctness limit of a cell is set from.

    python3 bench/limits.py --workload <cell> --seeds 11,12,13 --seconds 20

For each seed, in one process and on one engine: weights made from the
seed, the cell's closed loop served for ``--seconds`` after the ramp, and a
sample of the requests finished in that window compared with the float32
reference, as a benchmark run does. Two numbers per seed:

* ``program``: the widest reference gap of the served tokens (the number a
  run compares with its limit);
* ``control``: the widest reference gap of the token that the fp8 control
  (the reference with float8 weights and bfloat16 activations) puts first
  at the same positions of the same prompts and served tokens.

Each reading then goes through the run's own verdict (``bench.run.judge``)
with the cell's limit, as though its tokens had been served:
``program_correct`` has to hold and ``control_correct`` has to fail. The
limit goes above the largest ``program`` reading and below the smallest
``control`` one (PERF.md). The engine is built once and its
parameters swapped between seeds, so the programs it compiled serve every
seed; its KV pools are freed while the reference runs, as a run frees
the engine, and made anew for the next seed. Nothing here is timed.
"""
from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import loadgen, reference  # noqa: E402
from bench.run import (GRACE_S, SAMPLE_REQUESTS, SAMPLE_TOKENS,  # noqa: E402
                       Cell, Loop, NoChip, NO_CHIP, build_engine,
                       device_info, enable_compile_cache, judge, log,
                       sample_finished)


def readings(cell: Cell, seeds, seconds: float, require_chip: bool = True):
    import jax
    import jax.numpy as jnp
    from bench.model import make_weights
    device_info(cell.chips, require_chip)
    enable_compile_cache()
    limit = cell.config["check"]["max_logit_gap"]
    engine = None
    out = []
    for k, seed in enumerate(seeds):
        if engine is not None:
            # one set of weights on the chip at a time
            engine.params = None
            gc.collect()
        params = make_weights(cell.arch, cell.config, seed)
        if engine is None:
            engine = build_engine(cell, params)
        else:
            engine.params = params
        loop = Loop(engine, loadgen.RequestStream(cell.mix, seed), 2 + k,
                    cell.config["vocab_size"], cell.mix.clients)
        loop.start()
        loop.run(lambda now: loop.ramped)
        t0 = time.perf_counter()
        loop.run(lambda now: now - t0 >= seconds)
        t1 = loop.finish_enough(t0, seed, time.perf_counter() + GRACE_S)
        loop.close()
        engine.drain_requests()
        pools = jax.tree.map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=a.sharding),
            engine.pools)
        engine.pools = None
        gc.collect()
        sample = sample_finished(loop.order, t0, t1, seed, SAMPLE_TOKENS,
                                 SAMPLE_REQUESTS)
        prog = ctl = 0.0
        n = 0
        for r in sample:
            c = reference.control_tokens(cell.arch, params, cell.config,
                                         r.prompt, r.toks)
            g = reference.gaps(cell.arch, params, cell.config, r.prompt,
                               r.toks, {"control": c})
            prog = max(prog, float(g["served"].max()))
            ctl = max(ctl, float(g["control"].max()))
            n += len(c)
        row = {"seed": seed, "program": prog, "control": ctl, "tokens": n,
               "requests": len(sample),
               "program_correct": judge(prog, n, 0, 0, limit)[0],
               "control_correct": judge(ctl, n, 0, 0, limit)[0]}
        log(json.dumps(row))
        out.append(row)
        engine.pools = jax.tree.map(
            lambda s: jax.device_put(jnp.zeros(s.shape, s.dtype), s.sharding),
            pools)
        del params
        gc.collect()
    jax.block_until_ready(engine.pools)
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True,
                    help="comma-separated seeds")
    ap.add_argument("--seconds", type=float, default=20.0)
    args = ap.parse_args(argv)
    cell = Cell.load(ROOT, args.workload)
    try:
        rows = readings(cell, [int(s) for s in args.seeds.split(",")],
                        args.seconds)
    except NoChip as e:
        log(f"no result: {e}")
        return NO_CHIP
    lower = max(r["program"] for r in rows)
    upper = min(r["control"] for r in rows)
    print(json.dumps({"workload": cell.name, "lower": lower, "upper": upper,
                      "limit": cell.config["check"]["max_logit_gap"],
                      "rows": rows}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
