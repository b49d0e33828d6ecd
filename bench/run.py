"""Run one benchmark cell once, in this process, on the chips it finds.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

The cell (an entry of ``workloads`` in BENCHMARK.json) names a model
configuration (its ``file``) and a traffic mix (``bench/traffic/<mix>.json``).
The configuration's ``model_type`` names its architecture's module
(``bench/arch/<model_type>.py``), which gives the weight tree, the
program's ``ArchConfig``, the reference and the work counts. The run:

1. refuses to run without a TPU, or with fewer chips than the cell asks
   for: exit code 3, nothing on stdout;
2. makes bfloat16 weights of the architecture's tree on the device from
   the seed (``bench/model.py``) and builds the deployed serving path:
   ``AsyncDuetEngine`` over ``Model(attn_kernel=True)``, paged KV pools,
   prefix cache on;
3. warms up by serving the window's own request lengths (with other prompt
   tokens) for longer than the window lasts, so every program the window
   dispatches is compiled; then empties the engine with its public
   ``drain_requests`` and serves the same lengths again, this time timed,
   from the moment every client has its first token;
4. drives the engine only through ``submit(..., at=engine.now)`` and
   ``events()``: a closed loop in which each client sends its next request
   when its previous one finishes. Every time is the host clock when an
   event reaches the client;
5. after the window, frees the engine and compares a seeded sample of the
   requests finished in the window with the architecture's float32
   reference (``bench/reference.py``): ``correct`` holds when the widest
   gap between the reference's best logit and its logit of a served token
   is within the configuration's limit;
6. prints, as the last line of stdout, one JSON object: ``correct``,
   ``attempted``, ``failed``, ``metrics`` (the cell's end-to-end metrics
   with ``--trace 0``, its per-layer metrics with ``--trace 1``),
   ``device``, with ``--trace 1`` ``breakdown``, and last ``checked``.
   The compared numbers also go to stderr as its last lines.

Per-layer metrics are read by ``bench/metrics/<name>.py``.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from dataclasses import dataclass  # noqa: E402
from pathlib import Path  # noqa: E402
from types import ModuleType  # noqa: E402
from typing import Dict, List  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
if __name__ == "__main__":
    # the script's own directory would shadow standard modules by name
    sys.path[0] = str(ROOT)
    sys.path.insert(1, str(ROOT / "src"))

from bench import loadgen, readings  # noqa: E402
from bench.model import load_arch, load_config  # noqa: E402

WARM_FACTOR = 1.25    # warm-up serves this many windows' worth of work
GRACE_S = 90.0        # most serving after the window to finish requests
SAMPLE_TOKENS = 256   # served tokens the reference compares, at least
SAMPLE_REQUESTS = 6   # requests it compares, at most
NO_CHIP = 3           # exit code when the chips the cell needs are absent


class NoChip(Exception):
    pass


@dataclass
class Cell:
    name: str
    chips: int
    config: Dict
    arch: ModuleType
    config_name: str
    mix: loadgen.Mix
    end_to_end: List[Dict]
    per_layer: List[Dict]
    metrics_dir: Path

    @classmethod
    def load(cls, root: Path, name: str) -> "Cell":
        spec = json.loads((root / "BENCHMARK.json").read_text())
        cells = {w["name"]: w for w in spec["workloads"]}
        if name not in cells:
            raise KeyError(f"no workload {name!r}; have {sorted(cells)}")
        w = cells[name]
        c = {x["name"]: x for x in spec["configs"]}[w["config"]]
        config = load_config(root / c["file"])

        def mine(m):
            return "workloads" not in m or name in m["workloads"]
        return cls(name=name, chips=int(w["chips"]), config=config,
                   arch=load_arch(root, config, c["file"]),
                   config_name=c["name"],
                   mix=loadgen.Mix.load(
                       root / "bench" / "traffic" / f"{w['traffic']}.json"),
                   end_to_end=[m for m in spec["end_to_end"] if mine(m)],
                   per_layer=[m for m in spec["per_layer"] if mine(m)],
                   metrics_dir=root / "bench" / "metrics")

    def reader(self, metric: str):
        path = self.metrics_dir / f"{metric}.py"
        spec = importlib.util.spec_from_file_location(
            f"bench.metrics.{metric}", path)
        mod = importlib.util.module_from_spec(spec)
        spec.loader.exec_module(mod)
        return mod.read


def log(msg: str):
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


# ----------------------------------------------------------------- serving
class Loop:
    """Closed loop of ``mix.clients`` clients over one engine: a client
    sends its next request as soon as its previous one finishes."""

    def __init__(self, engine, stream: loadgen.RequestStream, pass_id: int,
                 vocab: int, clients: int):
        self.engine = engine
        self.stream = stream
        self.pass_id = pass_id
        self.vocab = vocab
        self.clients = clients
        self.recs: Dict[int, readings.Record] = {}
        self.reqs: Dict[int, object] = {}
        self.order: List[readings.Record] = []
        self.next_i = 0
        self.first_seen = 0
        self.gen = None

    def _submit(self):
        from repro.serving.request import Request
        i = self.next_i
        self.next_i += 1
        p, o = self.stream.lengths(i)
        toks = self.stream.tokens(self.pass_id, i, p, self.vocab)
        rid = self.pass_id * 100_000_000 + i
        r = Request(rid=rid, arrival=0.0, prompt_len=p, output_len=o)
        r.prompt_tokens = toks
        rec = readings.Record(rid=rid, prompt=toks, output_len=o,
                              submit_t=time.perf_counter())
        self.recs[rid] = rec
        self.reqs[rid] = r
        self.order.append(rec)
        self.engine.submit(r, at=self.engine.now)

    def start(self):
        for _ in range(self.clients):
            self._submit()
        self.gen = self.engine.events()

    def prefilled(self) -> Dict[int, int]:
        """Prompt tokens dispatched so far, by request (the engine counts
        a chunk when it dispatches it)."""
        return {rid: r.prefilled for rid, r in self.reqs.items()}

    @property
    def ramped(self) -> bool:
        """Every client's first request has its first token."""
        return self.first_seen >= self.clients

    def run(self, stop) -> None:
        """Consume events until ``stop(now)`` is true after an event. The
        engine's generator ends when a pass finds nothing to do, which it
        can decide before it sees requests submitted while it yields its
        last finish events: then a new generator serves them."""
        from repro.serving.async_engine import FinishEvent
        while True:
            got = False
            for ev in self.gen:
                got = True
                now = time.perf_counter()
                rec = self.recs[ev.rid]
                first = not rec.tok_t and rec.finish_t is None \
                    and rec.rid % 100_000_000 < self.clients
                self.first_seen += first
                if isinstance(ev, FinishEvent):
                    rec.finish_t, rec.reason = now, ev.reason
                    self._submit()
                else:
                    rec.tok_t.append(now)
                    rec.toks.append(int(ev.token))
                if stop(now):
                    return
            if not got:
                raise RuntimeError("the engine stopped serving a closed loop")
            self.gen = self.engine.events()

    def finish_enough(self, since: float, seed: int, until: float) -> float:
        """The check needs finished requests: where those finished since
        ``since`` do not yet give a sample (``sample_finished``) of
        SAMPLE_TOKENS served tokens, serve on, untimed, until they do or
        the clock reaches ``until``. Returns the time it stopped."""
        seen = [-1]

        def enough(now):
            done = sum(r.finish_t is not None for r in self.order)
            if done == seen[0] and now < until:
                return False
            seen[0] = done
            got = sample_finished(self.order, since, now, seed,
                                  SAMPLE_TOKENS, SAMPLE_REQUESTS)
            return sum(len(r.toks) for r in got) >= SAMPLE_TOKENS \
                or now >= until
        if not enough(time.perf_counter()):
            self.run(enough)
        return time.perf_counter()

    def close(self):
        if self.gen is not None:
            self.gen.close()
            self.gen = None


def counters(engine, clock) -> Dict[str, float]:
    ds, mx = engine.dstats, engine.mux.stats
    ps = engine.kv_mgr.prefix_stats()
    return {"super_iterations": ds.super_iterations,
            "dispatches": ds.dispatches, "host_syncs": ds.host_syncs,
            "cache_misses": ds.cache_misses,
            "mux_iterations": mx.iterations,
            "duet_iterations": mx.duet_iterations,
            "lookup_tokens": ps["lookup_tokens"],
            "hit_tokens": ps["hit_tokens"],
            "lowerings": clock.lowerings,
            "backend_compiles": clock.backend_compiles,
            "compile_s": clock.seconds}


def build_engine(cell: Cell, params):
    from repro.models.transformer import Model
    from repro.serving.async_engine import AsyncDuetEngine
    from repro.serving.engine import EngineConfig
    e = cell.config["engine"]
    model = Model(cell.arch.arch_config(cell.config, cell.config_name),
                  attn_kernel=True)
    ec = EngineConfig(max_slots=e["max_slots"],
                      token_budget=e["token_budget"], tbt_slo=e["tbt_slo"],
                      page_size=e["page_size"],
                      kv_pool_tokens=e["kv_pool_tokens"],
                      temperature=e["temperature"],
                      prefix_cache=e["prefix_cache"])
    return AsyncDuetEngine(model, params, ec)


# ------------------------------------------------------------- correctness
def sample_finished(recs: List[readings.Record], t0: float, t1: float,
                    seed: int, want_tokens: int, max_requests: int):
    """A seeded sample of the requests completed between ``t0`` and ``t1``,
    the one with the longest sequence first, until ``want_tokens`` served
    tokens."""
    done = [r for r in recs if r.finish_t is not None
            and t0 <= r.finish_t <= t1 and r.reason == "completed"]
    if not done:
        return []
    longest = max(done, key=lambda r: (r.prompt_len + len(r.toks), r.rid))
    rest = [r for r in done if r is not longest]
    rng = loadgen.sample_rng(seed)
    rest = [rest[i] for i in rng.permutation(len(rest))]
    out, n = [longest], len(longest.toks)
    for r in rest:
        if n >= want_tokens or len(out) >= max_requests:
            break
        out.append(r)
        n += len(r.toks)
    return out


def judge(gap: float, tokens: int, failed: int, short: int,
          limit: float):
    """``correct``, and each number compared beside its limit."""
    checked = {
        "max_logit_gap": {"value": gap, "limit": limit},
        "failed_requests": {"value": failed, "limit": 0},
        "short_outputs": {"value": short, "limit": 0},
        "compared_tokens": {"value": tokens, "limit": SAMPLE_TOKENS},
    }
    correct = (gap <= limit and failed == 0 and short == 0
               and tokens >= SAMPLE_TOKENS)
    return correct, checked


def check(cell: Cell, params, sample: List[readings.Record]) -> Dict:
    """Widest float32-reference gap of the sampled served tokens."""
    from bench import reference
    widest, n = 0.0, 0
    for r in sample:
        g = reference.gaps(cell.arch, params, cell.config, r.prompt,
                           r.toks)["served"]
        widest = max(widest, float(g.max()))
        n += len(g)
    return {"widest": widest, "tokens": n, "requests": len(sample)}


# ---------------------------------------------------------------------- run
CACHE_DIR = ROOT / ".jax_cache"


def enable_compile_cache():
    """JAX's persistent compilation cache in one fixed directory of the
    checkout (the path is part of the cache's key), with no size limit, so
    every run of a cell after the first fetches all its programs. None on
    the CPU, whose executables read back from a cache fail the host's
    feature check."""
    import jax
    if jax.default_backend() == "cpu":
        return None
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_compilation_cache_max_size", -1)
    return CACHE_DIR


def device_info(chips: int, require_chip: bool):
    import jax
    devs = jax.devices()
    if require_chip and (jax.default_backend() != "tpu" or len(devs) < chips):
        raise NoChip(f"cell needs {chips} TPU chip(s); JAX has "
                     f"{len(devs)} {jax.default_backend()} device(s)")
    return devs


def run(cell: Cell, seed: int, seconds: float, trace: bool,
        require_chip: bool = True) -> Dict:
    devs = device_info(cell.chips, require_chip)
    import jax
    from bench import devtrace, peaks
    from bench.clock import CompileClock
    from bench.model import make_weights

    kind = devs[0].device_kind
    peak = peaks.peak(kind) if require_chip else None
    enable_compile_cache()
    clock = CompileClock().install()

    t = time.perf_counter()
    params = make_weights(cell.arch, cell.config, seed)
    jax.block_until_ready(params)
    log(f"weights {time.perf_counter() - t:.2f} s")
    t = time.perf_counter()
    engine = build_engine(cell, params)
    log(f"engine {time.perf_counter() - t:.2f} s")
    vocab = cell.config["vocab_size"]
    stream = loadgen.RequestStream(cell.mix, seed)

    # warm-up: the same lengths, other prompts, for WARM_FACTOR windows of
    # non-compile time after the ramp
    t = time.perf_counter()
    warm = Loop(engine, stream, 1, vocab, cell.mix.clients)
    warm.start()
    warm.run(lambda now: warm.ramped)
    t_ramp, c_ramp = time.perf_counter(), clock.seconds
    warm.run(lambda now: (now - t_ramp) - (clock.seconds - c_ramp)
             >= WARM_FACTOR * seconds)
    warm_si = engine.dstats.super_iterations
    warm.close()
    engine.drain_requests()
    log(f"warm-up {time.perf_counter() - t:.2f} s, {warm_si} "
        f"super-iterations, {clock.lowerings} programs lowered, compile "
        f"{clock.seconds:.2f} s (backend {clock.backend_seconds:.2f} s, "
        f"{clock.backend_compiles} programs; persistent cache "
        f"{clock.cache_hits} hits, {clock.cache_misses} misses)")

    # the warm-up leaves a large heap of traced and lowered programs: a
    # full collection of it in the window stalls the host for a large
    # part of a second, so it is frozen out of the collector's scans
    gc.collect()
    gc.freeze()
    loop = Loop(engine, stream, 2, vocab, cell.mix.clients)
    loop.start()
    t = time.perf_counter()
    loop.run(lambda now: loop.ramped)
    log(f"ramp {time.perf_counter() - t:.2f} s")
    si0 = engine.dstats.super_iterations
    tracer = None
    if trace:
        tmp = tempfile.mkdtemp(prefix="bench-trace-")
        tracer = devtrace.Tracer(tmp)
        tracer.start()
    before = counters(engine, clock)
    pre0 = loop.prefilled()
    t0 = time.perf_counter()
    setup_s = t0 - T_PROCESS
    with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
        loop.run(lambda now: now - t0 >= seconds)
    t1 = time.perf_counter()
    after = counters(engine, clock)
    pre1 = loop.prefilled()
    jax.block_until_ready((engine.pools, engine.d_last_tok))
    profile = tracer.stop() if tracer else None
    delta = {k: after[k] - before[k] for k in after}
    log(f"window {t1 - t0:.2f} s, {engine.dstats.super_iterations - si0} "
        f"super-iterations (warm-up ran {warm_si}), {delta['lowerings']} "
        f"programs lowered; setup {setup_s:.2f} s")
    t_done = loop.finish_enough(t0, seed, t1 + GRACE_S)
    log(f"served {t_done - t1:.2f} s past the window")
    mem = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
              for d in devs[:cell.chips])

    recs = loop.order
    loop.close()
    del engine, loop, warm
    gc.unfreeze()
    gc.collect()

    # attempted: every request the window served a token to or ended
    served = [r for r in recs if any(t0 <= x <= t1 for x in r.tok_t)
              or (r.finish_t is not None and t0 <= r.finish_t <= t1)]
    in_window = [r for r in recs if r.finish_t is not None
                 and t0 <= r.finish_t <= t1]
    failed = sum(1 for r in in_window if r.reason != "completed")
    short = sum(1 for r in in_window
                if r.reason == "completed" and len(r.toks) != r.output_len)
    sample = sample_finished(recs, t0, t_done, seed, SAMPLE_TOKENS,
                             SAMPLE_REQUESTS)
    t = time.perf_counter()
    got = check(cell, params, sample)
    log(f"reference {time.perf_counter() - t:.2f} s over {got['requests']} "
        f"requests, {got['tokens']} tokens")
    correct, checked = judge(got["widest"], got["tokens"], failed, short,
                             cell.config["check"]["max_logit_gap"])

    device = {"platform": devs[0].platform, "kind": kind,
              "count": len(devs), "memory_peak_bytes": int(mem)}
    out = {"correct": bool(correct), "attempted": len(served),
           "failed": failed}
    if not trace:
        e2e = readings.end_to_end(recs, t0, t1, setup_s)
        log("end to end " + json.dumps(e2e))
        out["metrics"] = {m["name"]: {"value": e2e[m["name"]],
                                      "unit": m["unit"]}
                          for m in cell.end_to_end
                          if e2e.get(m["name"]) is not None}
    else:
        r = readings.Readings(
            window_s=t1 - t0, counters=delta, peak=peak,
            work=readings.window_work(recs, cell.arch, cell.config, t0,
                                      t1, pre0, pre1))
        bounds = devtrace.window_bounds(profile) if profile else None
        if bounds and devtrace.device_planes(profile):
            log("trace lines " + json.dumps(devtrace.describe(profile)))
            ops = devtrace.device_ops(profile)
            log(f"window span {bounds}, device ops from "
                f"{min(e[1] for e in ops)} to "
                f"{max(e[1] + e[2] for e in ops)}")
            r.trace = devtrace.reduce(ops, *bounds)
            mods = devtrace.clip(devtrace.device_modules(profile), *bounds)
            r.step_device_s = sum(d for _, _, d in mods) / 1e9
            device["busy_s"] = r.trace.busy_s
            device["window_s"] = r.trace.window_s
            top = sorted(r.trace.by_name.items(), key=lambda kv: -kv[1])
            out["breakdown"] = {
                "device_ops": [[n, s] for n, s in top[:10]],
                "idle_gaps": [list(g) for g in devtrace.name_gaps(
                    r.trace.gaps, devtrace.clip(devtrace.host_spans(profile),
                                                *bounds))]}
        shutil.rmtree(tracer.log_dir, ignore_errors=True)
        vals = {}
        for m in cell.per_layer:
            v = cell.reader(m["name"])(r)
            if v is not None:
                vals[m["name"]] = {"value": v, "unit": m["unit"]}
        out["metrics"] = vals
        log("counters " + json.dumps(delta))
    out["device"] = device
    out["checked"] = checked
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = Cell.load(ROOT, args.workload)
        out = run(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        log(f"no result: {e}")
        return NO_CHIP
    for name, c in out["checked"].items():
        log(f"check {name} = {c['value']} (limit {c['limit']})")
    log(f"run {time.perf_counter() - T_PROCESS:.2f} s")
    print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
