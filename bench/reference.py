"""The comparison with the plain reference that decides ``correct``, and
the numerics every architecture's reference shares.

The reference forward itself is the architecture's
(``bench/arch/<model_type>.py``: ``logits_at``), chosen by the
configuration's ``model_type``. It is independent of the program: it
imports only JAX and NumPy and reads the weights the benchmark made
(``bench/model.py``) by their names, in float32 at ``highest`` matmul
precision.

The sequence is prompt plus served tokens, padded at the end to a bucket
(padding after the last real position changes nothing before it under the
causal mask).

``precision="fp8"`` is the control: the same computation with every weight
matrix rounded to float8 e4m3 (one scale per tensor, per quarter of the
output head) and activations in bfloat16, the step below the
configuration's bfloat16.
"""
from __future__ import annotations

from typing import Dict, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

BUCKET = 2048        # sequence lengths are padded up to a multiple of this
Q_BLOCK = 512        # query rows per attention block
ROW_BLOCK = 256      # logit rows per output-head block
F8_MAX = 448.0       # largest finite float8 e4m3 value
VOCAB_BLOCKS = 4     # output-head slices (the padded vocabulary divides)


def bucket(n: int) -> int:
    """Padded length, so that few lengths compile: a power of two from
    Q_BLOCK up to BUCKET, then the next multiple of BUCKET."""
    if n > BUCKET:
        return -(-n // BUCKET) * BUCKET
    return max(Q_BLOCK, 1 << (n - 1).bit_length())


def rms(x, w, eps):
    x32 = x.astype(jnp.float32)
    y = x32 * jax.lax.rsqrt(jnp.mean(x32 * x32, axis=-1, keepdims=True) + eps)
    return (y * w.astype(jnp.float32)).astype(x.dtype)


def weight_as(w, precision):
    """A weight matrix as the computation sees it."""
    if precision == "fp8":
        w32 = w.astype(jnp.float32)
        s = jnp.maximum(jnp.max(jnp.abs(w32)), 1e-30) / F8_MAX
        q = (w32 / s).astype(jnp.float8_e4m3fn).astype(jnp.float32) * s
        return q.astype(jnp.bfloat16)
    return w.astype(jnp.float32)


def act_dtype(precision):
    return jnp.bfloat16 if precision == "fp8" else jnp.float32


def served_positions(prompt_len: int, n_served: int) -> np.ndarray:
    """Positions whose logits predict served tokens 0..n_served-1."""
    return prompt_len - 1 + np.arange(n_served)


def gaps(arch, weights, cfg: Dict, prompt: np.ndarray,
         served: Sequence[int],
         others: Optional[Dict[str, np.ndarray]] = None
         ) -> Dict[str, np.ndarray]:
    """Float32 reference gaps under ``arch``'s reference: for each served
    position, the reference's best logit minus its logit of the served
    token (>= 0; 0 where the served token is the reference's argmax).
    ``others`` maps a name to alternative tokens at the same positions,
    gapped the same way."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    pos = served_positions(len(prompt), len(served))
    cands = {"served": served, **(others or {})}
    out = {k: np.zeros(len(served), np.float32) for k in cands}
    for sl, lg in arch.logits_at(weights, cfg, seq, pos):
        best = jnp.max(lg, axis=-1)
        for k, toks in cands.items():
            t = jnp.asarray(toks[sl])
            got = jnp.take_along_axis(lg, t[:, None], axis=-1)[:, 0]
            out[k][sl] = np.asarray(best - got)
    return out


def control_tokens(arch, weights, cfg: Dict, prompt: np.ndarray,
                   served: Sequence[int]) -> np.ndarray:
    """The token the fp8 control puts first at each served position, on
    the same prompt and served tokens."""
    served = np.asarray(served, np.int32)
    seq = np.concatenate([np.asarray(prompt, np.int32), served[:-1]])
    pos = served_positions(len(prompt), len(served))
    out = np.zeros(len(served), np.int32)
    for sl, lg in arch.logits_at(weights, cfg, seq, pos, precision="fp8"):
        out[sl] = np.asarray(jnp.argmax(lg, axis=-1))
    return out
