"""The float32 reference against the program's own forward pass, and the
fp8 control against the reference, at a size a test run holds."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from bench import model as bm
from bench import reference as ref
from bench.tests.tiny import tiny_arch, tiny_config

arch = tiny_arch()


@pytest.fixture(scope="module")
def setup():
    from repro.models.transformer import Model
    cfg = tiny_config()
    w32 = bm.make_weights(arch, cfg, 2**35 + 3, dtype=jnp.float32)
    return cfg, w32, Model(arch.arch_config(cfg, "tiny"))


def ref_logits(w, cfg, toks):
    out = np.zeros((len(toks), cfg["vocab_size"]), np.float32)
    for sl, lg in arch.logits_at(w, cfg, toks, np.arange(len(toks))):
        out[sl] = np.asarray(lg)
    return out


@pytest.mark.parametrize("tied", [False, True])
def test_reference_matches_model_forward(setup, tied):
    cfg, _, _ = setup
    from repro.models.transformer import Model
    cfg = dict(cfg, tie_word_embeddings=tied)
    w = bm.make_weights(arch, cfg, 11, dtype=jnp.float32)
    assert ("w_out" in w) != tied
    m = Model(arch.arch_config(cfg, "tiny"))
    toks = np.random.default_rng(0).integers(0, cfg["vocab_size"], 70)
    toks = toks.astype(np.int32)
    with jax.default_matmul_precision("highest"):
        want = np.asarray(m.forward(w, jnp.asarray(toks)[None]))[0]
    got = ref_logits(w, cfg, toks)
    np.testing.assert_allclose(got, want[:, :cfg["vocab_size"]],
                               rtol=1e-4, atol=1e-4)


def test_gaps_are_zero_for_the_reference_argmax(setup):
    cfg, w, _ = setup
    toks = np.random.default_rng(1).integers(0, cfg["vocab_size"], 40)
    prompt = toks[:20].astype(np.int32)
    # greedy continuation under the reference itself: every gap is 0
    served = []
    seq = list(prompt)
    for _ in range(6):
        lg = ref_logits(w, cfg, np.asarray(seq, np.int32))
        served.append(int(lg[-1].argmax()))
        seq.append(served[-1])
    g = ref.gaps(arch, w, cfg, prompt, served)["served"]
    assert g.shape == (6,) and np.all(g == 0.0)
    wrong = [(t + 1) % cfg["vocab_size"] for t in served]
    assert ref.gaps(arch, w, cfg, prompt, wrong)["served"].min() > 0.0


def test_fp8_control_reads_far_above_bfloat16(setup):
    """The control's widest gap lies well above that of the same forward
    in bfloat16, the precision the configuration states."""
    cfg, w32, m = setup
    wbf = jax.tree.map(lambda a: a.astype(jnp.bfloat16), w32)
    w = jax.tree.map(lambda a: a.astype(jnp.float32), wbf)
    rng = np.random.default_rng(2)
    bf_gap = ctl_gap = 0.0
    for _ in range(4):
        toks = rng.integers(0, cfg["vocab_size"], 200).astype(np.int32)
        prompt, cont = toks[:100], toks[100:]
        lg = np.asarray(m.forward(wbf, jnp.asarray(toks)[None]))[0]
        served = lg[99:199, :cfg["vocab_size"]].argmax(-1).astype(np.int32)
        # the served tokens' context is the prompt plus ``cont``: gap the
        # bfloat16 and fp8 argmaxes on the same sequence
        seq_served = cont
        ctl = np.zeros(100, np.int32)
        for sl, lgc in arch.logits_at(w, cfg, toks[:199],
                                      np.arange(99, 199), precision="fp8"):
            ctl[sl] = np.asarray(jnp.argmax(lgc, -1))
        g = ref.gaps(arch, w, cfg, prompt,
                     np.concatenate([seq_served[:1], seq_served[1:]]),
                     {"bf16": served, "control": ctl})
        bf_gap = max(bf_gap, float(g["bf16"].max()))
        ctl_gap = max(ctl_gap, float(g["control"].max()))
    assert ctl_gap > 3 * bf_gap
