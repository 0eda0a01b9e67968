"""The plain reference: the model's forward pass in float32.

Straightforward ``jax.numpy`` at ``HIGHEST`` matmul precision (true
float32 on a TPU), batch 1, no cache, no kernels and nothing of the
program: it reads the canonical weights of ``weights.py`` and follows
the published description of the model's family. The forward pass is
the family's (``bench/families``, ``logits``), built from the helpers
here (``_mm``, ``_fp8``, ``_rms``, ``_rope``), so that every family's
reference and control follow the same float32 and float8 rules.

``gaps`` teacher-forces the reference over a prompt and the tokens the
program served for it, and returns, for every served token, how far
that token's reference logit lies below the reference's best at that
position: 0 where the program chose the reference's own argmax. That is
the check of greedy tokens.

``nucleus`` is the check of sampled tokens: teacher-forced the same
way, it places every served token in the reference's own top-p nucleus
at the request's temperature. A sampler keeps a token while the mass of
the tokens more probable than it is below ``top_p``; ``nucleus`` returns
that mass minus ``top_p`` (above 0 where the token lies outside the
nucleus), and the token's mid-point probability integral transform in
the nucleus distribution (the mass before it plus half its own, over
the nucleus's mass): uniform on (0, 1), with mean 1/2, for tokens drawn
from the nucleus at that temperature.

The control (``control=True``) is the same computation with both inputs
of every matrix product rounded to float8 (e4m3): the weights with one
scale per output channel, the activations with one scale per token.
That is the precision one step below the configuration's bfloat16, as a
float8 matmul path would compute. For it ``gaps`` reads, at the same
positions, the gap of the token the control puts first.
"""
from __future__ import annotations

from functools import partial

import jax
import jax.numpy as jnp
import numpy as np

from bench import families

HI = jax.lax.Precision.HIGHEST
LEN_BUCKET = 256              # sequences are padded to a multiple


def _fp8(w, axis):
    """Round to float8 e4m3 with one scale per slice along ``axis``."""
    w = w.astype(jnp.float32)
    amax = jnp.max(jnp.abs(w), axis=axis, keepdims=True)
    scale = jnp.where(amax > 0, amax / 448.0, 1.0)
    return (w / scale).astype(jnp.float8_e4m3fn).astype(jnp.float32) * scale


def _mm(x, w, control):
    """x @ w in float32; with ``control`` both rounded to float8 first."""
    if control:
        return jnp.matmul(_fp8(x, -1), _fp8(w, -2), precision=HI)
    return jnp.matmul(x, w.astype(jnp.float32), precision=HI)


def _rms(x, scale, eps):
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * scale.astype(jnp.float32)


def _rope(x, pos, theta):
    """x [S, n, hd]: rotate the two halves of each head (HF rotate_half)."""
    hd = x.shape[-1]
    inv = 1.0 / (theta ** (jnp.arange(0, hd, 2, dtype=jnp.float32) / hd))
    ang = pos.astype(jnp.float32)[:, None] * inv[None, :]     # [S, hd/2]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :hd // 2], x[..., hd // 2:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


@partial(jax.jit, static_argnames=("control",))
def _gaps(ref, lg, served, control):
    best = ref.max(-1)
    tok = jnp.argmax(lg, -1) if control else served
    return best - jnp.take_along_axis(ref, tok[:, None], -1)[:, 0]


@jax.jit
def _nucleus(ref, served, temperature, top_p):
    p = jax.nn.softmax(ref / temperature, axis=-1)
    pt = jnp.take_along_axis(p, served[:, None], -1)[:, 0]
    above = jnp.sum(jnp.where(p > pt[:, None], p, 0.0), -1)
    ps = -jnp.sort(-p, axis=-1)
    keep = (jnp.cumsum(ps, -1) - ps) < top_p
    z = jnp.sum(jnp.where(keep, ps, 0.0), -1)
    return above - top_p, (above + pt / 2) / z


def _teacher_forced(w, conf, prompt, served, pad_to, control=False):
    """Reference logits (and, with ``control``, the control's) at every
    position where the program served a token, padded to ``pad_to =
    (length, tokens)`` (or to buckets), with the served tokens padded
    alike and their number."""
    prompt = np.asarray(prompt, np.int32)
    served = np.asarray(served, np.int32)
    n, P = len(served), len(prompt)
    seq = np.concatenate([prompt, served[:-1]])
    if pad_to is not None:
        S, n_pad = pad_to
    else:
        S = -(-len(seq) // LEN_BUCKET) * LEN_BUCKET
        n_pad = -(-n // 64) * 64
    tokens = np.zeros(S, np.int32)
    tokens[:len(seq)] = seq
    rows = np.full(n_pad, P - 1, np.int32)
    rows[:n] = P - 1 + np.arange(n)
    fam = families.of(conf)
    ref = fam.logits(w, conf, tokens, rows, control=False)
    lg = fam.logits(w, conf, tokens, rows, control=True) if control else ref
    tok = np.zeros(n_pad, np.int32)
    tok[:n] = served
    return ref, lg, jnp.asarray(tok), n


def gaps(w, conf: dict, prompt, served, control: bool = False,
         pad_to=None) -> np.ndarray:
    """Per served token: reference best logit minus the reference logit of
    the program's token (or, with ``control``, of the control's first
    choice at that position). ``pad_to = (length, tokens)`` pads every
    sequence to one shape, so that a run compiles the reference once."""
    ref, lg, tok, n = _teacher_forced(w, conf, prompt, served, pad_to,
                                      control)
    return np.asarray(_gaps(ref, lg, tok, control))[:n]


def nucleus(w, conf: dict, prompt, served, temperature: float,
            top_p: float, pad_to=None):
    """Per served token: (mass of the reference's tokens more probable
    than it at ``temperature``, minus ``top_p``; its mid-point integral
    transform in the reference's nucleus distribution)."""
    ref, _, tok, n = _teacher_forced(w, conf, prompt, served, pad_to)
    excess, pit = _nucleus(ref, tok, jnp.float32(temperature),
                           jnp.float32(top_p))
    return np.asarray(excess)[:n], np.asarray(pit)[:n]
