"""Chip smoke: serve qwen3-4b at full width on a TPU through ``serve.py``.

Drives the serving entry point (``repro.launch.serve``) in this one process
with random bf16 weights made from a seed, and checks what comes out:

* ``device``       — JAX must see a TPU; there is no CPU fallback.
* ``serve_sync``   — the default path: synchronous engine, paged KV, prefix
  cache on, jnp attention. Every request finishes, none is rejected, the
  page pool drains.
* ``serve_kernel`` — the same requests through the async engine with the
  Pallas paged-decode (split-KV included) and fused duet kernels. Every
  request gets a finish event, at most one host sync per super-iteration,
  and the greedy tokens agree with ``serve_sync``.

``--chips 4`` runs only the multi-chip paths and their one-chip reference:
tensor parallelism over four chips, and four one-chip replicas behind the
router, each on its own device.

Timings are the host clock (``block_until_ready`` on the last outputs);
``predicted_*`` figures are the engine's roofline clock. The last line of
stdout is ``{"ok": true, "device": {...}}``; any failed check exits non-zero
before it.

Usage (needs the repo's ``src`` beside this file)::

    python3 chip_smoke.py              # one chip
    python3 chip_smoke.py --chips 4    # four chips
"""
from __future__ import annotations

import argparse
import contextlib
import gc
import io
import json
import os
import sys
import time

ARCH = "qwen3-4b"
# 8 requests from the azure-conv trace; seed 3 clamps every prompt to 256
# tokens (max_len // 2) and outputs to at most 128, so few shape buckets
# compile. The budget fits all prompts in one iteration each.
SERVE_ARGS = ["--arch", ARCH, "--trace", "azure-conv", "--num-requests", "8",
              "--qps", "1000", "--max-len", "512", "--max-slots", "8",
              "--token-budget", "2048", "--seed", "3"]
# a 32-page decode table holds 512 tokens; above 256 the split-KV kernel runs
SPLIT_KV_THRESHOLD = 256
# least share of decode steps on a shared context where the compared path
# picks the reference's greedy token. In bf16 with random weights a near-tie
# flips about 3% of steps (0.97 measured on a v5e); a split-KV kernel that
# drops its last split agrees on 0.80 (reduced config, CPU interpret mode)
MIN_STEP_MATCH = 0.9


class SmokeFailure(Exception):
    pass


def check(cond: bool, what: str):
    if not cond:
        raise SmokeFailure(what)


def emit(phase: str, **fields):
    print(json.dumps({"phase": phase, **fields}), flush=True)


class CompileClock:
    """Host seconds JAX spends tracing, lowering and compiling, summed from
    its monitoring events (the serve wall time includes them). The backend
    share is kept apart: only it can be read back from a compile cache."""

    BACKEND = "/jax/core/compile/backend_compile_duration"
    EVENTS = ("/jax/core/compile/jaxpr_trace_duration",
              "/jax/core/compile/jaxpr_to_mlir_module_duration", BACKEND)

    def __init__(self):
        self.seconds = self.backend_seconds = 0.0
        self.compiles = 0

    def __call__(self, event: str, duration: float, **_):
        if event in self.EVENTS:
            self.seconds += duration
        if event == self.BACKEND:
            self.backend_seconds += duration
            self.compiles += 1

    def take(self):
        out = (round(self.seconds, 3), round(self.backend_seconds, 3),
               self.compiles)
        self.seconds = self.backend_seconds = 0.0
        self.compiles = 0
        return out


def run_serve(serve, clock: CompileClock, argv):
    """One in-process serve; returns (summary, tokens by rid, server,
    captured stdout lines)."""
    clock.take()
    buf = io.StringIO()
    with contextlib.redirect_stdout(buf):
        out, reqs, server = serve(argv)
    out["compile_s"], out["backend_compile_s"], out["compiles"] = clock.take()
    tokens = {r.rid: [int(t) for t in r.output_tokens] for r in reqs}
    return out, tokens, server, buf.getvalue().splitlines()


def agreement(ref: dict, got: dict) -> dict:
    """Greedy-token agreement of two runs over the reference's requests.

    ``token_match``: share of positions with equal tokens. Once one greedy
    token differs, the rest of that request decodes from another context,
    so this share mostly measures where the first difference fell.
    ``step_match``: share of decode steps, taken on a context both runs
    share (up to and including each request's first difference), that
    pick the same token — an estimate of how often the two paths agree on
    the same input, which is what the bound checks.
    """
    total = same = steps = agreed = 0
    for rid, toks in ref.items():
        other = got.get(rid, [])
        total += len(toks)
        same += sum(a == b for a, b in zip(toks, other))
        common = 0
        while (common < min(len(toks), len(other))
               and toks[common] == other[common]):
            common += 1
        agreed += common
        steps += common + (common < len(toks))
    return {"token_match": round(same / max(1, total), 4),
            "step_match": round(agreed / max(1, steps), 4)}


def timings(out: dict) -> dict:
    return {"setup_s": out["wall_setup_s"], "serve_s": out["wall_serve_s"],
            "compile_s": out["compile_s"],
            "backend_compile_s": out["backend_compile_s"],
            "compiles": out["compiles"],
            "predicted_p50_ttft_s": out["p50_ttft_s"],
            "predicted_p50_tbt_s": out["p50_tbt_s"]}


def check_served(phase: str, out: dict, tokens: dict):
    check(out["num_requests"] == 8, f"{phase}: {out['num_requests']} "
          "requests served, expected 8")
    check(out["num_finished"] == out["num_requests"],
          f"{phase}: {out['num_finished']}/{out['num_requests']} finished")
    check(out["num_rejected"] == 0, f"{phase}: {out['num_rejected']} "
          "rejected")
    check(all(tokens.values()), f"{phase}: a request produced no tokens")


def device_phase(jax, chips: int) -> dict:
    devs = jax.devices()
    dev = {"platform": devs[0].platform, "kind": devs[0].device_kind,
           "count": len(devs)}
    check(jax.default_backend() == "tpu",
          f"JAX backend is {jax.default_backend()!r}, not a TPU: {dev}")
    check(len(devs) >= chips, f"{len(devs)} device(s), {chips} needed")
    emit("device", **dev)
    return dev


def one_chip_phases(serve, clock, jax):
    from repro.kernels.ops import default_interpret, num_splits_for
    from repro.configs import get_config

    cfg = get_config(ARCH)
    out, ref, engine, _ = run_serve(serve, clock, SERVE_ARGS)
    check_served("serve_sync", out, ref)
    check(engine.kv_mgr.used_pages == 0,
          f"serve_sync: {engine.kv_mgr.used_pages} pages still in use")
    check(out["kernel_path"] == "jnp", "serve_sync: not the jnp path")
    check(engine.cfg.num_layers == cfg.num_layers
          and engine.cfg.d_model == cfg.d_model
          and engine.cfg.vocab_size == cfg.vocab_size,
          "serve_sync: not the full-width config")
    dtype = str(engine.params["embedding"].dtype)
    pool_dtype = str(engine.pools[0][0].dtype)
    check(dtype == pool_dtype == "bfloat16",
          f"serve_sync: params {dtype}, pools {pool_dtype}; want bf16")
    emit("serve_sync", layers=engine.cfg.num_layers,
         d_model=engine.cfg.d_model, vocab=engine.cfg.vocab_size,
         dtype=dtype, requests=out["num_requests"],
         finished=out["num_finished"], rejected=out["num_rejected"],
         used_pages=engine.kv_mgr.used_pages,
         prefix_hit_tokens=out["prefix_cache"]["hit_tokens"],
         **timings(out))
    del engine
    gc.collect()

    check(not default_interpret(), "Pallas interpret mode is on")
    out, got, engine, lines = run_serve(
        serve, clock, SERVE_ARGS + ["--stream", "--attn-kernel",
                                    "--split-kv-threshold",
                                    str(SPLIT_KV_THRESHOLD)])
    check_served("serve_kernel", out, got)
    events = [json.loads(ln) for ln in lines if ln.startswith('{"event"')]
    finished = {e["rid"] for e in events if e["event"] == "finish"}
    check(finished == set(got), f"serve_kernel: finish events for "
          f"{sorted(finished)}, requests {sorted(got)}")
    ds = out["dispatch_stats"]
    check(ds["host_syncs"] <= ds["super_iterations"],
          f"serve_kernel: {ds['host_syncs']} host syncs > "
          f"{ds['super_iterations']} super-iterations")
    check(out["kernel_path"] == "pallas",
          f"serve_kernel: kernel_path {out['kernel_path']!r}")
    # program keys: (paged, kb, width, chunk, pwidth, finish, sample, duet);
    # a decode step through paged_decode_auto runs in every program with
    # kb > 1, or kb == 1 without the duet grid
    page = engine.ec.page_size
    split = [k for k in engine._programs
             if k[1] > (1 if k[-1] else 0)
             and num_splits_for(k[2], page, SPLIT_KV_THRESHOLD) > 1]
    duet = [k for k in engine._programs if k[-1]]
    check(split, "serve_kernel: the split-KV kernel never ran")
    agree = agreement(ref, got)
    emit("serve_kernel", finished=out["num_finished"],
         finish_events=len(finished), host_syncs=ds["host_syncs"],
         super_iterations=ds["super_iterations"],
         kernel_path=out["kernel_path"], splitkv_programs=len(split),
         duet_programs=len(duet), **agree, min_step_match=MIN_STEP_MATCH,
         **timings(out))
    check(agree["step_match"] >= MIN_STEP_MATCH,
          f"serve_kernel: step match {agree['step_match']} < "
          f"{MIN_STEP_MATCH}")


def four_chip_phases(serve, clock, jax):
    out, ref, engine, _ = run_serve(serve, clock, SERVE_ARGS)
    check_served("tp1", out, ref)
    emit("tp1", **timings(out))
    del engine
    gc.collect()

    out, got, engine, _ = run_serve(serve, clock, SERVE_ARGS + ["--tp", "4"])
    check_served("tp4", out, got)
    check(out["mesh"]["tp"] == 4, f"tp4: mesh {out['mesh']}")
    pool_devs = len(engine.pools[0][0].sharding.device_set)
    check(pool_devs == 4, f"tp4: page pool on {pool_devs} devices")
    agree = agreement(ref, got)
    emit("tp4", mesh=out["mesh"], pool_devices=pool_devs, **agree,
         min_step_match=MIN_STEP_MATCH, **timings(out))
    check(agree["step_match"] >= MIN_STEP_MATCH,
          f"tp4: step match {agree['step_match']} < {MIN_STEP_MATCH}")
    del engine
    gc.collect()

    out, got, router, _ = run_serve(serve, clock, SERVE_ARGS + ["--dp", "4"])
    check_served("dp4", out, got)
    homes = []
    for e in router.engines:
        devs = set()
        for leaf in jax.tree.leaves((e.params, e.pools)):
            devs |= leaf.sharding.device_set
        homes.append(sorted(d.id for d in devs))
    check(all(len(h) == 1 for h in homes) and len({h[0] for h in homes}) == 4,
          f"dp4: replica devices {homes}")
    check(all(e.kv_mgr.used_pages == 0 for e in router.engines),
          "dp4: a replica's page pool did not drain")
    emit("dp4", replica_devices=homes,
         per_replica_finished=[r["num_finished"]
                               for r in out["per_replica"]],
         **agreement(ref, got), **timings(out))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--chips", type=int, choices=(1, 4), default=1,
                    help="1: serve on one chip; 4: only the tensor- and "
                         "data-parallel paths on four chips")
    args = ap.parse_args(argv)

    src = os.path.join(os.path.dirname(os.path.abspath(__file__)), "src")
    sys.path.insert(0, src)
    try:
        import jax
        from repro.launch.compile_cache import enable_compile_cache
        from repro.launch.serve import serve
    except ImportError as e:
        print(f"chip_smoke: cannot import the serving stack from {src}: {e}",
              file=sys.stderr)
        return 1

    t0 = time.perf_counter()
    try:
        dev = device_phase(jax, args.chips)
        enable_compile_cache()
        clock = CompileClock()
        jax.monitoring.register_event_duration_secs_listener(clock)
        if args.chips == 4:
            four_chip_phases(serve, clock, jax)
        else:
            one_chip_phases(serve, clock, jax)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    emit("total", wall_s=round(time.perf_counter() - t0, 3))
    print(json.dumps({"ok": True, "device": dev}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
