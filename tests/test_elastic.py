"""Elastic data-parallelism (ISSUE 10): policy unit pins + sim-vs-real
scaling-decision parity.

Pinned contracts:
  * ElasticPolicy decision table: up over the per-replica threshold, down
    under the hysteresis floor, cooldown gates both directions, scale-up
    activates the lowest inactive index, scale-down drains the
    least-loaded non-zero replica (replica 0 is never drained);
  * ClusterSim on the calibrated load_sweep geometry produces the pinned
    alternating up/down sequence and loses no requests;
  * the REAL elastic router (dp=2 engines in a subprocess) and ClusterSim
    share the same (action, replica) scaling sequence AND the same
    dispatch-replica sequence on a burst-then-silence trace — the shared
    ElasticPolicy keeps scaling decisions pinned the way dispatch
    decisions already are.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.serving.loadgen import make_load
from repro.serving.router import ElasticConfig, ElasticPolicy

# Burst-then-silence constants shared verbatim with the subprocess driver:
# 10 requests land 2 ms apart, each worth ~30 ms of modeled service time
# (outstanding work piles far over the up threshold within the burst),
# then nothing — the drain phase empties the cluster and the down
# threshold fires.
N_REQ, GAP, PLEN, OLEN = 10, 0.002, 120, 40
ECFG = dict(min_replicas=1, max_replicas=2, scale_up_tokens=100,
            scale_down_tokens=20, cooldown_s=0.05, check_interval=0.05)


# ------------------------------------------------------------ policy pins
def _policy(**kw):
    return ElasticPolicy(ElasticConfig(**{**ECFG, **kw}))


def test_scale_up_over_threshold_lowest_inactive():
    p = _policy(max_replicas=4)
    assert p.decide([150, 0, 0, 0], [0], t=0.0) == ("up", 1)
    # next inactive index after another up
    assert p.decide([150, 80, 0, 0], [0, 1], t=1.0) == ("up", 2)


def test_no_scale_up_at_max_replicas():
    p = _policy()
    p.decide([500, 0], [0], t=0.0)
    assert p.decide([500, 500], [0, 1], t=10.0) is None


def test_scale_down_under_floor_least_loaded_victim():
    p = _policy(max_replicas=3)
    assert p.decide([10, 5, 2], [0, 1, 2], t=0.0) == ("down", 2)
    # ties break on index; replica 0 is never the victim even when idle
    p2 = _policy(max_replicas=3)
    assert p2.decide([0, 7, 7], [0, 1, 2], t=0.0) == ("down", 1)


def test_replica_zero_never_drained():
    # replica 1 is the victim even though replica 0 carries LESS load:
    # replica 0 anchors the cluster and is never drained
    p = _policy()
    assert p.decide([2, 10], [0, 1], t=0.0) == ("down", 1)
    # a lone replica 0 can never be drained below min_replicas
    p2 = _policy()
    assert p2.decide([0, 0], [0], t=0.0) is None


def test_hysteresis_band_holds():
    # between the thresholds: no action either way
    p = _policy()
    assert p.decide([60, 0], [0], t=0.0) is None          # 60 <= 100
    assert p.decide([15, 35], [0, 1], t=0.0) is None      # 50 > 20


def test_cooldown_gates_both_directions():
    p = _policy(cooldown_s=0.2)
    assert p.decide([500, 0], [0], t=0.0) == ("up", 1)
    # inside the cooldown window nothing fires, even a clear down
    assert p.decide([0, 0], [0, 1], t=0.1) is None
    assert p.decide([0, 0], [0, 1], t=0.3) == ("down", 1)


def test_config_validation():
    with pytest.raises(ValueError):
        ElasticConfig(min_replicas=3, max_replicas=2)
    with pytest.raises(ValueError):
        ElasticConfig(min_replicas=0, max_replicas=2)
    with pytest.raises(ValueError):
        ElasticConfig(check_interval=0.0)


# ------------------------------------------------- ClusterSim pinned run
def test_cluster_sim_pinned_scaling_sequence():
    from repro.configs import get_config
    from repro.serving.simulator import (ClusterSim, SimConfig,
                                         make_duet_instance)
    cfg = get_config("qwen3-4b")
    reqs = make_load("azure-conv", process="mmpp", qps=2.19,
                     burst_factor=6.0, mean_burst_s=20.0, mean_calm_s=40.0,
                     seed=0).generate(60)
    sim = ClusterSim(
        lambda i: make_duet_instance(cfg, SimConfig(units=1, tp=1),
                                     token_budget=8192),
        n=2, policy="least-loaded",
        elastic=ElasticConfig(min_replicas=1, max_replicas=2,
                              scale_up_tokens=600, scale_down_tokens=250,
                              cooldown_s=5.0, check_interval=1.0))
    m = sim.run(reqs)
    seq = [(e.action, e.replica) for e in sim.scale_events]
    # the calibrated geometry breathes twice: up in each burst, down in
    # each lull — and replica 1 is always the elastic one
    assert seq == [("up", 1), ("down", 1), ("up", 1), ("down", 1)]
    assert m.summary()["num_finished"] == 60
    # event invariants: active set reflects each action, times increase
    for e in sim.scale_events:
        assert (1 in e.active) == (e.action == "up")
    ts = [e.t for e in sim.scale_events]
    assert ts == sorted(ts)


# ------------------------------------------- sim-vs-real decision parity
DRIVER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import copy
    import json
    import jax
    from repro.configs import get_config, reduced
    from repro.core.device import DeviceContext
    from repro.models.transformer import Model
    from repro.serving.engine import DuetEngine, EngineConfig
    from repro.serving.request import Request
    from repro.serving.router import ElasticConfig, Router
    from repro.serving.simulator import (ClusterSim, SimConfig,
                                         make_duet_instance)

    N_REQ, GAP, PLEN, OLEN = 10, 0.002, 120, 40
    ECFG = dict(min_replicas=1, max_replicas=2, scale_up_tokens=100,
                scale_down_tokens=20, cooldown_s=0.05, check_interval=0.05)

    cfg = reduced(get_config("qwen3-4b"))

    def burst_trace():
        return [Request(rid=i, arrival=i * GAP, prompt_len=PLEN,
                        output_len=OLEN) for i in range(N_REQ)]

    # --- real elastic router: dp=2 engines, round-robin dispatch --------
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    router = Router(model, params,
                    EngineConfig(max_slots=4, max_len=256, token_budget=64),
                    ctx=DeviceContext.for_shape(cfg, tp=1, dp=2),
                    policy="round-robin", elastic=ElasticConfig(**ECFG))
    router.submit(burst_trace())
    m = router.run()

    # --- ClusterSim: same trace, same policy objects --------------------
    sim = ClusterSim(
        lambda i: make_duet_instance(cfg, SimConfig(units=1, tp=1),
                                     token_budget=64),
        n=2, policy="round-robin", elastic=ElasticConfig(**ECFG))
    sim_m = sim.run(burst_trace())

    results = {
        "real_scale": [(e.action, e.replica) for e in router.scale_events],
        "sim_scale": [(e.action, e.replica) for e in sim.scale_events],
        "real_dispatch": [d.replica for d in router.decisions],
        "sim_dispatch": [d.replica for d in sim.decisions],
        "real_finished": m.summary()["num_finished"],
        "sim_finished": sim_m.summary()["num_finished"],
        "real_rids": sorted(r.rid for r in m.requests
                            if r.finish_time is not None),
        "real_generated_ok": all(r.generated == r.output_len
                                 for r in m.requests),
        "elastic_summary": router.router_summary()["elastic"],
    }
    print("RESULT " + json.dumps(results))
""")


@pytest.fixture(scope="module")
def parity():
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(os.path.dirname(__file__), "..", "src")
    # the child runs on host devices only: never on a chip its parent holds
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", DRIVER], env=env,
                          capture_output=True, text=True, timeout=540)
    assert proc.returncode == 0, proc.stderr[-3000:]
    line = [ln for ln in proc.stdout.splitlines()
            if ln.startswith("RESULT ")][0]
    return json.loads(line[len("RESULT "):])


def test_real_elastic_run_scales_and_loses_nothing(parity):
    seq = [tuple(e) for e in parity["real_scale"]]
    assert ("up", 1) in seq and ("down", 1) in seq
    assert parity["real_finished"] == N_REQ
    assert parity["real_rids"] == list(range(N_REQ))
    assert parity["real_generated_ok"], \
        "a drained request resumed with the wrong generation target"
    es = parity["elastic_summary"]
    assert es["scale_ups"] >= 1 and es["scale_downs"] >= 1
    assert es["final_active"] == [0]


def test_sim_vs_real_scaling_decisions_pinned(parity):
    # the shared ElasticPolicy + identical control grid => identical
    # (action, replica) sequences, real engines vs simulator
    assert parity["real_scale"] == parity["sim_scale"]
    assert parity["sim_finished"] == N_REQ


def test_sim_vs_real_dispatch_sequence_pinned(parity):
    # dispatch over the breathing active subset stays pinned too
    assert parity["real_dispatch"] == parity["sim_dispatch"]
    assert len(parity["real_dispatch"]) >= N_REQ
