"""Cluster router (ISSUE 5 tentpole): dp=2 real replicas behind pluggable
dispatch.

Pinned contracts:
  * round-robin dispatch is token-identical to two independent
    single-replica engines fed the same shards (the ClusterSim parity
    oracle);
  * prefix-affinity routes a shared-system-prompt pair trace to the warm
    replica — nonzero cluster hit rate where round-robin's is ~zero;
  * least-loaded rebalances a skewed trace (policy unit tests + ClusterSim);
  * ClusterSim and the real router share dispatch decisions (sim parity).

Everything multi-device runs in a subprocess that forces 8 host devices
(the main test session keeps its single device — see conftest). The async
(streaming) cluster pays extra super-iteration compiles and is marked slow.
"""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

# Pair-trace constants shared verbatim with the subprocess driver: 3 groups
# of 2 requests; each group shares a 32-token prefix (two full default
# pages), the pair's second member arrives after the first's prefill
# completes. Round-robin splits every pair across the two replicas (zero
# cross-request hits); prefix affinity reunites them.
GROUPS, SHARED, GROUP_GAP, PAIR_GAP = 3, 32, 1.5, 0.5

DRIVER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import copy
    import json
    import numpy as np
    import jax
    from repro.configs import get_config, reduced
    from repro.core.device import DeviceContext
    from repro.launch.mesh import make_mesh, make_test_mesh, split_data_axis
    from repro.models.transformer import Model
    from repro.serving.async_engine import AsyncDuetEngine, TokenEvent
    from repro.serving.engine import DuetEngine, EngineConfig
    from repro.serving.request import Request, synth_prompt_tokens
    from repro.serving.router import Router
    from repro.serving.simulator import (ClusterSim, SimConfig,
                                         make_duet_instance)

    mode = sys.argv[1]
    results = {}
    cfg = reduced(get_config("qwen3-4b"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    EC = dict(max_slots=4, max_len=256, token_budget=64)

    GROUPS, SHARED, GROUP_GAP, PAIR_GAP = 3, 32, 1.5, 0.5

    def pair_trace():
        reqs = []
        for g in range(GROUPS):
            common = np.random.default_rng(1000 + g).integers(
                0, cfg.vocab_size, SHARED).astype(np.int32)
            for j in range(2):
                rid = 2 * g + j
                plen = 36 + 4 * g
                body = synth_prompt_tokens(rid, cfg.vocab_size, plen)
                reqs.append(Request(
                    rid=rid, arrival=g * GROUP_GAP + j * PAIR_GAP,
                    prompt_len=plen + SHARED, output_len=6 + g,
                    prompt_tokens=np.concatenate([common, body])))
        return reqs

    def toks_of(metrics):
        return {str(r.rid): [int(t) for t in r.output_tokens]
                for r in metrics.requests}

    def run_router(policy, engine_cls=DuetEngine):
        router = Router(model, params, EngineConfig(**EC),
                        ctx=DeviceContext.for_shape(cfg, tp=1, dp=2),
                        policy=policy, engine_cls=engine_cls)
        router.submit([copy.deepcopy(r) for r in pair_trace()])
        events = []
        m = router.run(on_event=events.append)
        return router, m, events

    if mode == "fast":
        reqs = pair_trace()

        # --- independent single-replica engines on the RR shards --------
        indep = {}
        indep_hits = 0
        for shard in (reqs[0::2], reqs[1::2]):
            eng = DuetEngine(model, params, EngineConfig(**EC))
            eng.submit([copy.deepcopy(r) for r in shard])
            indep.update(toks_of(eng.run()))
            indep_hits += eng.kv_mgr.prefix_stats()["hit_tokens"]

        # --- round-robin router: token parity + ~zero hits --------------
        rr, rr_m, _ = run_router("round-robin")
        results["rr_match"] = toks_of(rr_m) == indep
        results["rr_finished"] = rr_m.summary()["num_finished"]
        results["rr_hit_tokens"] = rr.prefix_stats()["hit_tokens"]
        results["rr_indep_hit_tokens"] = indep_hits
        results["rr_replicas"] = [d.replica for d in rr.decisions]

        # --- prefix-affinity router: warm-replica routing ---------------
        pf, pf_m, _ = run_router("prefix")
        results["pf_finished"] = pf_m.summary()["num_finished"]
        results["pf_hit_tokens"] = pf.prefix_stats()["hit_tokens"]
        results["pf_hit_rate"] = pf.prefix_stats()["hit_rate"]
        results["pf_decisions"] = [
            {"rid": d.rid, "replica": d.replica, "matched": d.matched_tokens}
            for d in pf.decisions]
        s = pf.summary()
        results["pf_summary_keys"] = sorted(
            k for k in ("router", "per_replica", "slo_attainment")
            if k in s)
        results["pf_dispatch_counts"] = s["router"]["dispatch_counts"]

        # --- sim parity: ClusterSim shares the dispatch decisions -------
        sim = ClusterSim(
            lambda i: make_duet_instance(
                cfg, SimConfig(units=1, tp=1), token_budget=64),
            n=2, policy="prefix")
        sim.run([copy.deepcopy(r) for r in reqs])
        results["sim_replicas"] = [d.replica for d in sim.decisions]
        results["sim_matched"] = [d.matched_tokens for d in sim.decisions]
        results["real_matched"] = [d.matched_tokens for d in pf.decisions]

        # --- split_replicas geometry ------------------------------------
        ctx = DeviceContext.for_shape(cfg, tp=2, dp=2)
        subs = ctx.split_replicas()
        ids = [sorted(d.id for d in c.mesh.devices.flat) for c in subs]
        results["split"] = {
            "n": len(subs),
            "tp": [c.tp for c in subs], "dp": [c.dp for c in subs],
            "disjoint": not (set(ids[0]) & set(ids[1])),
            "covers": sorted(ids[0] + ids[1])
            == sorted(d.id for d in ctx.mesh.devices.flat),
        }
        try:
            split_data_axis(make_mesh((2, 2), ("model", "data")))
            results["bad_axis_raises"] = False
        except ValueError:
            results["bad_axis_raises"] = True

    elif mode == "stream":
        # async replicas: the streamed cluster token events must match the
        # synchronous round-robin cluster (itself the independent oracle)
        _, sync_m, _ = run_router("round-robin")
        _, async_m, events = run_router("round-robin",
                                        engine_cls=AsyncDuetEngine)
        streamed = {}
        for ev in events:
            if isinstance(ev, TokenEvent):
                streamed.setdefault(str(ev.rid), []).append(ev.token)
        results["match"] = toks_of(async_m) == toks_of(sync_m)
        results["stream_match"] = streamed == toks_of(sync_m)
        results["n_token_events"] = sum(len(v) for v in streamed.values())

    print("RESULT " + json.dumps(results))
""")


def _drive(mode: str) -> dict:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the child runs on host devices only: never on a chip its parent holds
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", DRIVER, mode], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


@pytest.fixture(scope="module")
def fast():
    return _drive("fast")


def test_round_robin_token_identical_to_independent_replicas(fast):
    assert fast["rr_match"], \
        "dp=2 round-robin diverged from independent single-replica engines"
    assert fast["rr_finished"] == 2 * GROUPS
    # blind dispatch = strict alternation
    assert fast["rr_replicas"] == [i % 2 for i in range(2 * GROUPS)]


def test_prefix_affinity_beats_round_robin_hit_rate(fast):
    # round-robin splits every pair across replicas: no cross-request hits
    # (neither in the router cluster nor in the independent oracle)
    assert fast["rr_hit_tokens"] == 0
    assert fast["rr_indep_hit_tokens"] == 0
    # prefix affinity reunites the pairs on the warm replica: every
    # second member hits its group's full shared prefix
    assert fast["pf_hit_tokens"] >= GROUPS * SHARED
    assert fast["pf_hit_tokens"] > fast["rr_hit_tokens"]
    assert fast["pf_hit_rate"] > 0
    assert fast["pf_finished"] == 2 * GROUPS


def test_prefix_affinity_routes_pairs_to_warm_replica(fast):
    by_rid = {d["rid"]: d for d in fast["pf_decisions"]}
    for g in range(GROUPS):
        first, second = by_rid[2 * g], by_rid[2 * g + 1]
        assert first["matched"] == 0, first
        assert second["matched"] >= SHARED, second
        assert second["replica"] == first["replica"], (first, second)
    assert sum(fast["pf_dispatch_counts"]) == 2 * GROUPS
    assert fast["pf_summary_keys"] == ["per_replica", "router",
                                       "slo_attainment"]


def test_cluster_sim_parity_with_real_router(fast):
    # identical dispatch policy implementations + identical trace =>
    # identical decision sequences (replica and matched-token per rid)
    assert fast["sim_replicas"] == [d["replica"]
                                    for d in fast["pf_decisions"]]
    assert fast["sim_matched"] == fast["real_matched"]
    assert sum(fast["sim_matched"]) >= GROUPS * SHARED


def test_split_replicas_geometry(fast):
    split = fast["split"]
    assert split["n"] == 2
    assert split["tp"] == [2, 2] and split["dp"] == [1, 1]
    assert split["disjoint"] and split["covers"]
    assert fast["bad_axis_raises"]


@pytest.mark.slow
def test_async_cluster_stream_matches_sync_oracle():
    r = _drive("stream")
    assert r["match"], "async dp=2 cluster diverged from the sync cluster"
    assert r["stream_match"], "streamed token events diverged from metrics"
    assert r["n_token_events"] > 0


# --------------------------------------------------------------- policies
class _StubView:
    page_size = 16

    def __init__(self, outstanding=0, matched=0):
        self._o, self._m = outstanding, matched

    def outstanding_tokens(self):
        return self._o

    def match_keys(self, keys):
        return self._m


def test_least_loaded_policy_balances_and_tiebreaks():
    from repro.serving.router import LeastLoadedPolicy
    p = LeastLoadedPolicy()
    views = [_StubView(100), _StubView(10), _StubView(10)]
    idx, matched = p.choose(views, None)
    assert (idx, matched) == (1, 0)          # least load, lowest index tie
    p.record(1)
    idx, _ = p.choose(views, None)
    assert idx == 2                          # dispatch-count tie-break


def test_prefix_policy_prefers_longest_match_then_load():
    from repro.serving.router import PrefixAffinityPolicy
    p = PrefixAffinityPolicy()
    ids = np.arange(64)
    views = [_StubView(0, 32), _StubView(50, 64), _StubView(5, 64)]
    idx, matched = p.choose(views, ids)
    assert (idx, matched) == (2, 64)         # longest match, then load
    # no match anywhere -> least-loaded fallback
    cold = [_StubView(9, 0), _StubView(3, 0)]
    assert p.choose(cold, ids) == (1, 0)
    # no token ids -> fallback too
    assert p.choose(cold, None) == (1, 0)


def test_cluster_sim_least_loaded_rebalances_skewed_trace():
    from repro.configs import get_config, reduced
    from repro.serving.request import Request
    from repro.serving.simulator import (ClusterSim, SimConfig,
                                         make_duet_instance)
    cfg = reduced(get_config("qwen3-4b"))
    # alternating heavy/light arrivals in one burst: round-robin piles
    # every heavy request onto replica 0
    reqs = [Request(rid=i, arrival=0.001 * i,
                    prompt_len=2000 if i % 2 == 0 else 100,
                    output_len=8)
            for i in range(8)]

    work = {r.rid: r.prompt_len + r.output_len for r in reqs}
    spreads = {}
    for policy in ("round-robin", "least-loaded"):
        sim = ClusterSim(
            lambda i: make_duet_instance(cfg, SimConfig(units=1, tp=1)),
            n=2, policy=policy)
        sim.run(reqs)
        per = [0, 0]
        for d in sim.decisions:
            per[d.replica] += work[d.rid]
        spreads[policy] = abs(per[0] - per[1])
    assert spreads["least-loaded"] < spreads["round-robin"]
