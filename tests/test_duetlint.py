"""duetlint: per-rule true-positive/true-negative fixtures + machinery.

Each rule is pinned on a minimal fixture that MUST fire (TP) and a
semantically-equivalent-but-legal fixture that MUST stay silent (TN),
plus the real-tree checks the acceptance criteria name: the host-sync
rule against the real ``async_engine.py`` single-fetch site, and a
clean full run over ``src/`` modulo the checked-in baseline.
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, REPO)

from tools.duetlint.core import (Project, load_baseline, run,  # noqa: E402
                                 write_baseline)
from tools.duetlint.rules import ALL_RULES, get_rules  # noqa: E402


def lint(tmp_path, tree, rules=(), config=None):
    """Write a fixture tree, lint it, return the report."""
    for rel, src in tree.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    project = Project.from_paths([str(tmp_path)], config=config)
    return run(project, get_rules(list(rules)))


def messages(report):
    return [f.message for f in report.findings]


# ---------------------------------------------------------------------------
# rule 1: host-sync


HOT_SYNC_TP = """
    import jax
    import jax.numpy as jnp
    import numpy as np

    class Engine:
        def step(self, x):
            logits = jnp.dot(x, x)
            tok = int(jnp.argmax(logits))        # cast on device value
            v = float(logits[0])                  # cast on tainted name
            host = np.asarray(logits)             # device -> host copy
            got = jax.device_get(logits)          # raw fetch
            logits.block_until_ready()            # pipeline stall
            s = logits.item()                     # scalar fetch
            return tok, v, host, got, s
"""

HOT_SYNC_TN = """
    import jax.numpy as jnp
    import numpy as np

    class Engine:
        def step(self, host_list):
            n = int(len(host_list))               # host int: fine
            arr = np.asarray(host_list)           # host -> host: fine
            dev = jnp.asarray(arr)                # host -> device: fine
            host = np.asarray(dev)                # flagged if unbaselined,
            m = float(host[0])                    # ...but host after conv
            return dev, m

        def cold_path(self, x):
            return x
"""


def test_host_sync_true_positive(tmp_path):
    report = lint(tmp_path, {"serving/engine.py": HOT_SYNC_TP},
                  rules=["host-sync"])
    msgs = messages(report)
    assert sum("int() on device value" in m for m in msgs) == 1
    assert sum("float() on device value" in m for m in msgs) == 1
    assert sum("np.asarray() on device value" in m for m in msgs) == 1
    assert sum("device_get outside" in m for m in msgs) == 1
    assert sum("block_until_ready" in m for m in msgs) == 1
    assert sum(".item() on device value" in m for m in msgs) == 1


def test_host_sync_true_negative(tmp_path):
    report = lint(tmp_path, {"serving/engine.py": HOT_SYNC_TN},
                  rules=["host-sync"])
    msgs = messages(report)
    # exactly the one real device->host conversion fires; the host-side
    # int()/float()/np.asarray uses around it must stay silent
    assert len(msgs) == 1 and "np.asarray() on device value" in msgs[0]


def test_host_sync_ignores_cold_modules(tmp_path):
    report = lint(tmp_path, {"models/util.py": HOT_SYNC_TP},
                  rules=["host-sync"])
    assert report.findings == []


def test_host_sync_real_async_engine_single_fetch_site():
    """The real async engine: exactly one device_get, allowlisted."""
    target = os.path.join(REPO, "src/repro/serving/async_engine.py")
    clean = run(Project.from_paths([target]), get_rules(["host-sync"]))
    assert clean.findings == []
    strict = run(Project.from_paths(
        [target], config={"host-sync": {"allowed_sites": ()}}),
        get_rules(["host-sync"]))
    fetches = [f for f in strict.findings
               if "device_get" in f.message]
    assert len(fetches) == 1
    assert fetches[0].symbol == "AsyncDuetEngine._drain_record"


# ---------------------------------------------------------------------------
# rule 2: tier-transitions


TIER_TP = """
    import enum

    class PageTier(enum.Enum):
        FREE = 0
        HBM_ACTIVE = 1
        HBM_CACHED = 2
        HOST_CACHED = 3

    _TIER_TRANSITIONS = {
        (PageTier.FREE, PageTier.HBM_ACTIVE),
        (PageTier.HBM_ACTIVE, PageTier.FREE),
        (PageTier.HBM_ACTIVE, PageTier.HBM_CACHED),
    }

    class Pool:
        def _set_tier(self, page, new):
            self._tier[page] = new

        def activate(self, page):
            self._set_tier(page, PageTier.HBM_ACTIVE)

        def release(self, page):
            self._set_tier(page, PageTier.FREE)

        def demote(self, page):
            self._set_tier(page, PageTier.HOST_CACHED)   # no inbound edge

        def sneaky(self, page):
            self._tier[page] = PageTier.FREE             # bypasses setter
"""

TIER_TN = """
    import enum

    class PageTier(enum.Enum):
        FREE = 0
        HBM_ACTIVE = 1

    _TIER_TRANSITIONS = {
        (PageTier.FREE, PageTier.HBM_ACTIVE),
        (PageTier.HBM_ACTIVE, PageTier.FREE),
    }

    class Pool:
        def __init__(self):
            self._tier = {}

        def _set_tier(self, page, new):
            self._tier[page] = new

        def activate(self, page):
            self._set_tier(page, PageTier.HBM_ACTIVE)

        def release(self, page):
            self._set_tier(page, PageTier.FREE)
"""


def test_tier_transitions_true_positive(tmp_path):
    report = lint(tmp_path, {"serving/kvcache.py": TIER_TP},
                  rules=["tier-transitions"])
    msgs = messages(report)
    assert any("no inbound edge" in m for m in msgs)
    assert any("bypasses _set_tier" in m for m in msgs)
    # HBM_CACHED edge is declared but never targeted by a call site
    assert any("has no _set_tier() call site" in m for m in msgs)


def test_tier_transitions_true_negative(tmp_path):
    report = lint(tmp_path, {"serving/kvcache.py": TIER_TN},
                  rules=["tier-transitions"])
    assert report.findings == []


def test_tier_transitions_real_kvcache_clean():
    target = os.path.join(REPO, "src/repro/serving/kvcache.py")
    report = run(Project.from_paths([target]),
                 get_rules(["tier-transitions"]))
    assert report.findings == []


# ---------------------------------------------------------------------------
# rule 3: lock-balance


LOCK_TP = """
    class Engine:
        def admit(self, r):
            self.kv_mgr.allocate(r.rid, r.len)

        def _retire(self, r):
            if r.slot >= 0:
                self.kv_mgr.free(r.rid)     # conditional: leak path exists
            self.done.append(r)

        def _preempt(self, r):
            try:
                self.checkpoint(r)
                self.kv_mgr.free(r.rid)
            except ValueError:
                return                       # exception edge leaks

        def _reject(self, r):
            self.kv_mgr.free(r.rid)
"""

LOCK_TN = """
    class Engine:
        def admit(self, r):
            self.kv_mgr.allocate(r.rid, r.len)
            self.kv_mgr.lock_prefix(r.rid, r.prompt)

        def _retire(self, r):
            self.kv_mgr.free(r.rid)
            self.done.append(r)

        def _preempt(self, r):
            try:
                self.checkpoint(r)
            finally:
                self.kv_mgr.free(r.rid)      # covers the exception edge

        def _reject(self, r):
            if r.slot >= 0:
                self.kv_mgr.free(r.rid)
                return
            self.kv_mgr.free(r.rid)
"""

LOCK_MISSING = """
    class Engine:
        def admit(self, r):
            self.kv_mgr.reserve_lookahead(r.rid, 4)

        def _retire(self, r):
            self.kv_mgr.free(r.rid)

        def _preempt(self, r):
            self.kv_mgr.free(r.rid)
"""


def test_lock_balance_true_positive(tmp_path):
    report = lint(tmp_path, {"serving/engine.py": LOCK_TP},
                  rules=["lock-balance"])
    bad = {f.symbol for f in report.findings}
    assert "Engine._retire" in bad          # conditional free
    assert "Engine._preempt" in bad         # exception edge
    assert "Engine._reject" not in bad


def test_lock_balance_true_negative(tmp_path):
    report = lint(tmp_path, {"serving/engine.py": LOCK_TN},
                  rules=["lock-balance"])
    assert report.findings == []


def test_lock_balance_missing_release_method(tmp_path):
    report = lint(tmp_path, {"serving/engine.py": LOCK_MISSING},
                  rules=["lock-balance"])
    assert any("defines no _reject()" in m for m in messages(report))


def test_lock_balance_real_engines_clean():
    targets = [os.path.join(REPO, "src/repro/serving/engine.py"),
               os.path.join(REPO, "src/repro/serving/async_engine.py")]
    report = run(Project.from_paths(targets), get_rules(["lock-balance"]))
    assert report.findings == []


# ---------------------------------------------------------------------------
# rule 4: recompile-hazard


RECOMPILE_TP = """
    import jax

    class Engine:
        def _program(self, x, tbl):
            key = (x.shape, len(tbl), [x.ndim])
            prog = self._programs.get(key)
            return prog

        def statics(self, g, a, tbl):
            f = jax.jit(g, static_argnums=(1,))
            return f(a, tbl.shape)
"""

RECOMPILE_TN = """
    class Engine:
        def _program(self, n, w):
            key = (self.paged, self._k_bucket(n), self._table_width(w))
            prog = self._programs.get(key)
            return prog

        def lookup(self, k):
            if k not in self._decode_fns:
                self._decode_fns[k] = self.build(k)
            return self._decode_fns[k]
"""


def test_recompile_hazard_true_positive(tmp_path):
    report = lint(tmp_path, {"serving/engine.py": RECOMPILE_TP},
                  rules=["recompile-hazard"])
    msgs = messages(report)
    assert any("raw `.shape`" in m for m in msgs)
    assert any("raw len()" in m for m in msgs)
    assert any("unhashable list" in m for m in msgs)
    assert any("jit static argument" in m for m in msgs)


def test_recompile_hazard_true_negative(tmp_path):
    report = lint(tmp_path, {"serving/engine.py": RECOMPILE_TN},
                  rules=["recompile-hazard"])
    assert report.findings == []


def test_recompile_hazard_real_async_engine_clean():
    target = os.path.join(REPO, "src/repro/serving/async_engine.py")
    report = run(Project.from_paths([target]),
                 get_rules(["recompile-hazard"]))
    assert report.findings == []


# ---------------------------------------------------------------------------
# rule 5: donation-after-use


DONATE_TP = """
    import jax

    def make_step():
        return jax.jit(_step, donate_argnums=(1,))

    def make_wrapped():
        return make_step()          # transitive factory

    class Engine:
        def run(self, x):
            fn = make_wrapped()
            out = fn(x, self.buf)
            return self.buf + out   # read of consumed buffer
"""

DONATE_TN = """
    import jax

    def make_step():
        return jax.jit(_step, donate_argnums=(1,))

    class Engine:
        def run(self, x):
            fn = make_step()
            out, self.buf = fn(x, self.buf)   # same-statement rebind
            return self.buf + out
"""


def test_donation_after_use_true_positive(tmp_path):
    report = lint(tmp_path, {"core/engine.py": DONATE_TP},
                  rules=["donation-after-use"])
    msgs = messages(report)
    assert len(msgs) == 1
    assert "`self.buf` read after being donated" in msgs[0]


def test_donation_after_use_true_negative(tmp_path):
    report = lint(tmp_path, {"core/engine.py": DONATE_TN},
                  rules=["donation-after-use"])
    assert report.findings == []


def test_donation_real_tree_clean():
    # the real engines rebind every donated buffer in the same statement
    targets = [os.path.join(REPO, "src/repro/core/lookahead.py"),
               os.path.join(REPO, "src/repro/serving/engine.py"),
               os.path.join(REPO, "src/repro/serving/async_engine.py")]
    report = run(Project.from_paths(targets),
                 get_rules(["donation-after-use"]))
    assert report.findings == []


def test_donation_real_engine_program_is_a_donating_factory():
    # the clean run above checks the engine's dispatch only while its
    # `_program` resolves, through make_superiter_fn, as a donating factory
    from tools.duetlint.rules.donation import _factories
    targets = [os.path.join(REPO, "src/repro/core/lookahead.py"),
               os.path.join(REPO, "src/repro/serving/async_engine.py")]
    factories = _factories(Project.from_paths(targets))
    assert factories["make_superiter_fn"]
    assert factories["_program"] == factories["make_superiter_fn"]


# ---------------------------------------------------------------------------
# rule 6: pallas-hygiene


PALLAS_TP = """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref):
        v = pl.load(x_ref, (0, 0))            # no mask on ragged dim
        pl.store(o_ref, (0, 0), v)            # no mask either

    def build(f):
        grid = (4, 2)
        return pl.pallas_call(
            f,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((8, 128), lambda i, j: (i, j)),
                ],
                out_specs=pl.BlockSpec((8, 128),
                                       lambda s, i, j: (i, j, 0)),
            ),
        )
"""

PALLAS_TN = """
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    def kernel(x_ref, o_ref, mask_ref):
        v = pl.load(x_ref, (0, 0), mask=mask_ref[0])
        pl.store(o_ref, (0, 0), v, mask=mask_ref[0])

    def build(f):
        grid = (4, 2)
        return pl.pallas_call(
            f,
            grid_spec=pltpu.PrefetchScalarGridSpec(
                num_scalar_prefetch=1,
                grid=grid,
                in_specs=[
                    pl.BlockSpec((8, 128), lambda s, i, j: (i, j)),
                ],
                out_specs=pl.BlockSpec((8, 128),
                                       lambda s, i, j: (i, j)),
            ),
        )
"""


PALLAS_ARITY_TP = """
    from jax.experimental import pallas as pl

    def build(f, grid):                       # grid unresolvable: a param
        return pl.pallas_call(
            f,
            grid=grid,
            in_specs=[
                pl.BlockSpec((8, 128), lambda i, j: (i, j)),
                pl.BlockSpec((8, 128), lambda i, j, k: (i, j)),
            ],
            out_specs=pl.BlockSpec((8, 128), lambda i, j, k: (i, j)),
        )
"""

PALLAS_DIV_TP = """
    from jax.experimental import pallas as pl
    import jax.numpy as jnp

    def kernel(acc_ref, l_ref, o_ref):
        j = pl.program_id(0)

        @pl.when(j == 7)
        def _epilogue():
            o_ref[0] = acc_ref[...] / l_ref[...]      # 0-denominator NaNs

        pl.when(j == 8)(lambda: pl.store(
            o_ref, (0,), acc_ref[...] / pl.load(l_ref, (0,), mask=None),
            mask=None))
"""

PALLAS_DIV_TN = """
    from jax.experimental import pallas as pl
    import jax.numpy as jnp

    DENOM_EPS = 1e-20

    def kernel(acc_ref, l_ref, o_ref):
        j = pl.program_id(0)

        @pl.when(j == 7)
        def _epilogue():
            denom = jnp.maximum(l_ref[...], DENOM_EPS)[..., None]
            o_ref[0] = acc_ref[...] / denom
"""


def test_pallas_hygiene_true_positive(tmp_path):
    report = lint(tmp_path, {"kernels/broken.py": PALLAS_TP},
                  rules=["pallas-hygiene"])
    msgs = messages(report)
    assert sum("without mask=" in m for m in msgs) == 2
    assert any("takes 2 args" in m and "expected 3" in m for m in msgs)
    assert any("returns 3 indices for a rank-2 block" in m for m in msgs)


def test_pallas_hygiene_true_negative(tmp_path):
    report = lint(tmp_path, {"kernels/ok.py": PALLAS_TN},
                  rules=["pallas-hygiene"])
    assert report.findings == []


def test_pallas_hygiene_arity_consistency(tmp_path):
    report = lint(tmp_path, {"kernels/mixed.py": PALLAS_ARITY_TP},
                  rules=["pallas-hygiene"])
    msgs = messages(report)
    assert sum("other index maps in the same pallas_call" in m
               for m in msgs) == 1
    assert any("takes 2 args" in m and "take 3" in m for m in msgs)


def test_pallas_hygiene_epilogue_division(tmp_path):
    report = lint(tmp_path, {"kernels/div.py": PALLAS_DIV_TP},
                  rules=["pallas-hygiene"])
    msgs = messages(report)
    assert sum("division by a raw ref read" in m for m in msgs) == 2


def test_pallas_hygiene_guarded_division_clean(tmp_path):
    report = lint(tmp_path, {"kernels/ok_div.py": PALLAS_DIV_TN},
                  rules=["pallas-hygiene"])
    assert report.findings == []


def test_pallas_hygiene_outside_kernels_ignored(tmp_path):
    report = lint(tmp_path, {"serving/helper.py": PALLAS_TP},
                  rules=["pallas-hygiene"])
    assert report.findings == []


def test_pallas_hygiene_real_kernels_clean():
    target = os.path.join(REPO, "src/repro/kernels")
    report = run(Project.from_paths([target]),
                 get_rules(["pallas-hygiene"]))
    assert report.findings == []


# ---------------------------------------------------------------------------
# suppressions, baseline, CLI


def test_inline_suppression(tmp_path):
    src = HOT_SYNC_TP.replace(
        "got = jax.device_get(logits)          # raw fetch",
        "got = jax.device_get(logits)  # duetlint: disable=host-sync")
    report = lint(tmp_path, {"serving/engine.py": src},
                  rules=["host-sync"])
    assert report.suppressed == 1
    assert not any("device_get" in m for m in messages(report))


def test_disable_next_suppression(tmp_path):
    src = HOT_SYNC_TP.replace(
        "got = jax.device_get(logits)          # raw fetch",
        "# duetlint: disable-next=host-sync\n"
        "            got = jax.device_get(logits)")
    report = lint(tmp_path, {"serving/engine.py": src},
                  rules=["host-sync"])
    assert report.suppressed == 1


def test_baseline_round_trip_and_staleness(tmp_path):
    for rel, src in {"serving/engine.py": HOT_SYNC_TP}.items():
        path = tmp_path / rel
        path.parent.mkdir(parents=True, exist_ok=True)
        path.write_text(textwrap.dedent(src))
    project = Project.from_paths([str(tmp_path)])
    first = run(project, get_rules(["host-sync"]))
    assert first.findings

    bl = tmp_path / "baseline.json"
    write_baseline(str(bl), first.findings)
    entries = load_baseline(str(bl))
    assert all(e["justification"] for e in entries)

    second = run(project, get_rules(["host-sync"]), entries)
    assert second.findings == []
    assert len(second.baselined) == len(first.findings)
    assert second.stale_baseline == []

    entries.append({"rule": "host-sync", "path": "serving/gone.py",
                    "symbol": "X.y", "message": "m",
                    "justification": "was fixed"})
    third = run(project, get_rules(["host-sync"]), entries)
    assert len(third.stale_baseline) == 1


def test_baseline_requires_justification(tmp_path):
    bl = tmp_path / "baseline.json"
    bl.write_text(json.dumps({"entries": [
        {"rule": "host-sync", "path": "a.py", "symbol": "f",
         "message": "m"}]}))
    with pytest.raises(SystemExit):
        load_baseline(str(bl))


def test_rule_registry_complete():
    names = {r.name for r in ALL_RULES}
    assert names == {"host-sync", "tier-transitions", "lock-balance",
                     "recompile-hazard", "donation-after-use",
                     "pallas-hygiene"}
    with pytest.raises(SystemExit):
        get_rules(["no-such-rule"])


def test_cli_clean_on_src():
    """Acceptance: `python -m tools.duetlint src/` exits 0."""
    proc = subprocess.run(
        [sys.executable, "-m", "tools.duetlint", "src", "--format",
         "json"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0, proc.stdout + proc.stderr
    payload = json.loads(proc.stdout)
    assert payload["findings"] == []
    assert len(payload["baselined"]) >= 3      # the oracle-engine syncs


def test_cli_list_rules():
    proc = subprocess.run(
        [sys.executable, "-m", "tools.duetlint", "--list-rules"],
        cwd=REPO, capture_output=True, text=True)
    assert proc.returncode == 0
    for name in ("host-sync", "pallas-hygiene"):
        assert name in proc.stdout
