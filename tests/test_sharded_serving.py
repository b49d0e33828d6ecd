"""Mesh-aware serving (ISSUE 4 tentpole): TP=2 engines over the sharded
paged KV pool must be token-identical to the single-device oracle, with the
async engine's single-sync contract intact, CoW isolation holding on
sharded pools, and the mesh split/validation helpers sound.

Everything multi-device runs in a subprocess that forces 8 host devices
(the main test session keeps its single device — see conftest). One driver
invocation covers all fast scenarios; the preemption-resume case pays a
second engine compile and is marked slow.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

DRIVER = textwrap.dedent("""
    import os, sys
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import copy
    import json
    import numpy as np
    import jax
    from repro.configs import get_config, reduced
    from repro.core.device import DeviceContext
    from repro.launch.mesh import (data_axes, make_test_mesh,
                                   split_duet_submeshes)
    from repro.models.transformer import Model
    from repro.serving.async_engine import AsyncDuetEngine
    from repro.serving.engine import DuetEngine, EngineConfig
    from repro.serving.kvcache import (PagedKVCacheManager, PagePoolConfig,
                                       copy_pool_pages, init_page_pools)
    from repro.serving.request import Request, synth_prompt_tokens

    mode = sys.argv[1]
    results = {}
    cfg = reduced(get_config("qwen3-4b"))
    model = Model(cfg)
    params = model.init(jax.random.PRNGKey(0))
    ctx2 = DeviceContext.for_shape(cfg, tp=2)

    def shared_prefix_reqs(n=6, shared=32):
        # staggered arrivals so later requests hit the pages the first
        # prefill inserted; shared=32 is two full default pages
        common = np.random.default_rng(7).integers(
            0, cfg.vocab_size, shared).astype(np.int32)
        reqs = []
        for i in range(n):
            plen = 40 + 8 * (i % 3)
            body = synth_prompt_tokens(i, cfg.vocab_size, plen)
            reqs.append(Request(
                rid=i, arrival=0.05 * i, prompt_len=plen + shared,
                output_len=8 + (i % 4),
                prompt_tokens=np.concatenate([common, body])))
        return reqs

    def run(engine_cls, ctx, reqs, **ec_kw):
        kw = dict(max_slots=4, max_len=256, token_budget=64)
        kw.update(ec_kw)
        ec = EngineConfig(**kw)
        rs = [copy.deepcopy(r) for r in reqs]
        eng = engine_cls(model, params, ec, ctx=ctx)
        eng.submit(rs)
        metrics = eng.run()
        toks = {str(r.rid): [int(t) for t in r.output_tokens]
                for r in metrics.requests}
        return eng, metrics, toks

    if mode == "fast":
        reqs = shared_prefix_reqs()

        # --- sync engine: TP=2 == single-device (paged + prefix cache) --
        _, m0, t0 = run(DuetEngine, None, reqs)
        e2, m2, t2 = run(DuetEngine, ctx2, reqs)
        results["sync_match"] = t0 == t2
        results["sync_finished"] = m2.summary()["num_finished"]
        results["tp2_prefix_hit_tokens"] = \\
            e2.kv_mgr.prefix_stats()["hit_tokens"]

        # --- async engine: same oracle + single-sync contract under TP --
        _, _, at0 = run(AsyncDuetEngine, None, reqs)
        a2, _, at2 = run(AsyncDuetEngine, ctx2, reqs)
        results["async_match"] = at0 == t0 and at2 == t0
        results["async_syncs"] = a2.dstats.host_syncs
        results["async_super_iters"] = a2.dstats.super_iterations

        # --- CoW isolation on SHARDED pools ---------------------------
        # two requests share one fully-matched page; the second's first
        # write must privatise it without touching the cached original,
        # with the copy running as a sharded device op
        mgr = PagedKVCacheManager(PagePoolConfig(num_pages=16, page_size=4),
                                  prefix_cache=True)
        pools = init_page_pools(cfg, mgr.pool,
                                shardings=ctx2.pool_shardings())
        results["pool_devices"] = len(pools[0][0].sharding.device_set)
        toks4 = np.arange(1, 5, dtype=np.int64)      # one full page
        [page_a] = mgr.allocate(1, 4)
        pools = [None if p is None else
                 (p[0].at[page_a].set(1.0), p[1].at[page_a].set(1.0))
                 for p in pools]
        mgr.insert_prefix(1, toks4)
        matched = mgr.lock_prefix(2, toks4)
        copies = mgr.ensure_writable(2, matched)
        pools = copy_pool_pages(pools, copies)
        [(src, dst)] = copies
        pools = [None if p is None else
                 (p[0].at[dst, 3].set(9.0), p[1].at[dst, 3].set(9.0))
                 for p in pools]
        k0 = np.asarray(pools[0][0])
        results["cow"] = {
            "matched": matched,
            "cow_copies": mgr.stats.cow_copies,
            "src_intact": bool((k0[src] == 1.0).all()),
            "dst_prefix_copied": bool((k0[dst, :3] == 1.0).all()),
            "dst_written": bool((k0[dst, 3] == 9.0).all()),
        }

        # --- mesh split geometry + validation -------------------------
        mesh = make_test_mesh(2, 4)
        pre, dec = split_duet_submeshes(mesh, 1)
        pre_ids = {d.id for d in pre.devices.flat}
        dec_ids = {d.id for d in dec.devices.flat}
        all_ids = {d.id for d in mesh.devices.flat}
        results["split"] = {
            "pre_shape": dict(pre.shape), "dec_shape": dict(dec.shape),
            "disjoint": not (pre_ids & dec_ids),
            "covers": (pre_ids | dec_ids) == all_ids,
        }
        results["data_axes_pod"] = list(data_axes(make_test_mesh(2, 2,
                                                                 pod=2)))
        try:
            make_test_mesh(3, 3)
            results["oversub_raises"] = False
        except ValueError as e:
            results["oversub_raises"] = "xla_force_host" in str(e)
        try:
            split_duet_submeshes(mesh, 4)
            results["bad_split_raises"] = False
        except ValueError:
            results["bad_split_raises"] = True

    elif mode == "kernel":
        # --- Pallas kernel path under TP=2 (ISSUE 9 tentpole) ----------
        # the capability probe must resolve pallas (1 device) /
        # pallas_sharded (TP=2, shard_map over the KV-head axis) with NO
        # jnp fallback, and the token streams must stay byte-identical
        kmodel = Model(cfg, attn_kernel=True)
        kparams = kmodel.init(jax.random.PRNGKey(0))
        reqs = shared_prefix_reqs()

        def krun(engine_cls, ctx, rr=None, **ec_kw):
            kw = dict(max_slots=4, max_len=256, token_budget=64)
            kw.update(ec_kw)
            rs = [copy.deepcopy(r) for r in (reqs if rr is None else rr)]
            eng = engine_cls(kmodel, kparams, EngineConfig(**kw), ctx=ctx)
            eng.submit(rs)
            m = eng.run()
            return eng, {str(r.rid): [int(t) for t in r.output_tokens]
                         for r in m.requests}

        e1, t1 = krun(DuetEngine, None)
        e2, t2 = krun(DuetEngine, ctx2)
        results["kernel_paths"] = [e1.kernel_path, e2.kernel_path]
        results["kernel_model_attn"] = [e1.model.attn_kernel,
                                        e2.model.attn_kernel]
        results["kernel_tp2_match"] = t2 == t1
        results["kernel_finished"] = len([v for v in t2.values() if v])

        # async single-device: the duet-kernel fused program must hold the
        # one-device_get-per-super-iteration contract and stay identical.
        # Simultaneous arrivals with long outputs keep a decode batch
        # resident while later prompts prefill — the mixed-phase plans the
        # fused duet grid actually dispatches on
        dreqs = [Request(rid=100 + i, arrival=0.0,
                         prompt_len=40 + 8 * (i % 3),
                         output_len=16 + (i % 5)) for i in range(8)]
        s1, st1 = krun(DuetEngine, None, rr=dreqs, token_budget=48)
        a1, at1 = krun(AsyncDuetEngine, None, rr=dreqs, token_budget=48)
        results["kernel_async_match"] = at1 == st1
        results["kernel_async_syncs"] = a1.dstats.host_syncs
        results["kernel_async_super_iters"] = a1.dstats.super_iterations
        results["kernel_duet_buckets"] = len(
            [k for k in a1._programs if k[-1] is True])

        # an unusable kernel geometry always raises; nothing falls back
        badmodel = Model(cfg, attn_kernel=True)
        try:
            DuetEngine(badmodel, kparams,
                       EngineConfig(max_slots=4, max_len=256, paged=False),
                       ctx=ctx2)
            results["strict_raises"] = False
        except ValueError as e:
            results["strict_raises"] = "attn_kernel" in str(e)

    elif mode == "preempt":
        # tiny pool: look-ahead shrink + victim preemption + recompute
        # must still match the unconstrained single-device oracle under TP
        specs = [Request(rid=i, arrival=0.0, prompt_len=20, output_len=12)
                 for i in range(2)]
        _, mref, tref = run(DuetEngine, None, specs, max_len=64,
                            token_budget=32, page_size=4,
                            kv_pool_tokens=1024)
        e, m, t = run(DuetEngine, ctx2, specs, max_len=64,
                      token_budget=32, page_size=4, kv_pool_tokens=56)
        s = m.summary()
        results["match"] = t == tref
        results["finished"] = s["num_finished"]
        results["preemptions"] = s["num_preemptions"]
        results["pool_drained"] = e.kv_mgr.used_pages == 0

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


def test_tp2_sync_engine_token_identical(fast):
    assert fast["sync_match"], "TP=2 sync engine diverged from oracle"
    assert fast["sync_finished"] == 6
    # prefix cache active across the sharded pool
    assert fast["tp2_prefix_hit_tokens"] > 0


def test_tp2_async_engine_token_identical_single_sync(fast):
    assert fast["async_match"], "TP=2 async engine diverged from oracle"
    assert fast["async_syncs"] <= fast["async_super_iters"]


def test_sharded_cow_isolation(fast):
    cow = fast["cow"]
    assert fast["pool_devices"] == 2          # pool really is distributed
    assert cow["matched"] == 3 and cow["cow_copies"] == 1
    assert cow["src_intact"], "CoW wrote through to the cached page"
    assert cow["dst_prefix_copied"] and cow["dst_written"]


def test_split_geometry_and_mesh_validation(fast):
    split = fast["split"]
    assert split["pre_shape"] == {"data": 2, "model": 3}
    assert split["dec_shape"] == {"data": 2, "model": 1}
    assert split["disjoint"] and split["covers"]
    assert fast["data_axes_pod"] == ["pod", "data"]
    assert fast["oversub_raises"] is not False   # message names the fix
    assert fast["bad_split_raises"]


@pytest.fixture(scope="module")
def kernel():
    return _drive("kernel")


def test_tp2_kernel_path_resolves_sharded(kernel):
    """TP=2 with attn_kernel must keep the Pallas path (shard_map over the
    KV-head axis) — the old behavior was a blanket warn-and-fallback."""
    assert kernel["kernel_paths"] == ["pallas", "pallas_sharded"]
    assert kernel["kernel_model_attn"] == [True, True], \
        "the probe silently disabled the kernel path"


def test_tp2_kernel_token_identical(kernel):
    assert kernel["kernel_tp2_match"], \
        "TP=2 sharded kernel diverged from the single-device kernel oracle"
    assert kernel["kernel_finished"] == 6


def test_duet_kernel_async_single_sync(kernel):
    """The fused duet-kernel program keeps the async engine's contract:
    at most one blocking device_get per super-iteration, token-identical."""
    assert kernel["kernel_async_match"]
    assert kernel["kernel_async_syncs"] <= kernel["kernel_async_super_iters"]
    assert kernel["kernel_duet_buckets"] >= 1, \
        "no duet-fused program was ever dispatched"


def test_strict_kernel_raises_on_unusable_geometry(kernel):
    assert kernel["strict_raises"]


@pytest.mark.slow
def test_tp2_preemption_resume_matches_oracle():
    r = _drive("preempt")
    assert r["match"], "TP=2 preemption-resume diverged from oracle"
    assert r["finished"] == 2
    assert r["preemptions"] >= 1
    assert r["pool_drained"]
