"""Operations the algorithms need, counted from shapes.

These are the yardstick of every ``mfu`` metric: the work a call needs,
not what a compiler happened to emit, so a faster implementation of the
same mathematics cannot read above 100 %.  Each count says how it was
derived.  A floating-point add, subtract, multiply, divide, square root,
exponential, absolute value, maximum or minimum counts as one operation;
selects (``where``) and data movement count as none.
"""
from __future__ import annotations

from typing import Dict

# One interface of the x sweep of the hydrostatic-reconstruction scheme:
#   bed max 1; reconstructed depths 2 x (add, sub, max) = 6;
#   desingularised velocities: per side h^4 (2), max with eps^4 (1), add (1),
#   sqrt (1) = 5, shared by both momenta -> 10, then per momentum
#   sqrt(2) h (1) x q (1) / root (1) = 3, four momenta -> 12;
#   normal mass fluxes 2 mul; wave speeds 2 x (abs, mul, sqrt, add) = 8;
#   max of the two speeds 1;
#   mass flux: average (2) + a (h_r - h_l) / 2 (3) + sub (1) = 6;
#   normal-momentum flux: 2 mul + average (2) + a (m_r - m_l) / 2 (3) + sub = 8;
#   tangential flux: 4 mul + average (2) + 2 mul + a (...) / 2 (3) + sub = 12.
# Total per interface: 1 + 6 + 22 + 2 + 8 + 1 + 6 + 8 + 12 = 66.
SWE_FLOPS_PER_FACE = 66
# Per cell and sweep: three flux differences (3), the pressure term
# (2 x (sub, add, mul) + add + mul = 8), adding it (1), three divisions by
# the spacing (3) = 15.
SWE_FLOPS_PER_CELL_SWEEP = 15
# Per cell and step after both sweeps: three (add, mul by dt, sub) = 9,
# depth clamp 1, wet test 1 = 11.
SWE_FLOPS_PER_CELL_UPDATE = 11
# Per probe and step of the observation: eta = h + b (1), running max (1),
# sigmoid argument (sub, mul) 2, sigmoid (exp, add, div) 3, 1 - s (1),
# running product (1), running sum (1) = 10.
OBS_FLOPS_PER_PROBE_STEP = 10


def swe_flops_per_cell_step() -> int:
    """One cell, one step, both sweeps (one face per cell and sweep)."""
    return 2 * (SWE_FLOPS_PER_FACE + SWE_FLOPS_PER_CELL_SWEEP) + SWE_FLOPS_PER_CELL_UPDATE


def swe_forward_flops(nx: int, ny: int, n_steps: int, n_probes: int = 2) -> int:
    """One forward solve: the initial bump (about 8 operations a cell:
    two squared offsets, a sum, a scale, exp, height, and the wet-cell
    depth) plus ``n_steps`` steps over the grid and the observation."""
    cells = nx * ny
    return 8 * cells + n_steps * (cells * swe_flops_per_cell_step() + n_probes * OBS_FLOPS_PER_PROBE_STEP)


def gp_predict_flops(n_train: int, d: int = 2, p: int = 4) -> int:
    """Posterior mean at one point against ``n_train`` training points:
    per training point the scaled difference (2 d), its square and sum
    (2 d), sqrt (1), sqrt(5) r (1), 1 + s + s^2 / 3 (4), exp(-s) (2), the
    product with the output scale (2), and the contraction with alpha
    (2 p); then the output scaling (2 p)."""
    return n_train * (4 * d + 10 + 2 * p) + 2 * p


def lm_token_flops(cfg: Dict, context: int, head: bool) -> int:
    """One token of a dense decoder with grouped-query attention and a
    SwiGLU MLP, at ``context`` keys (the token itself included).

    Per layer: the q, k, v, o projections and the three MLP matrices, two
    operations per weight; q, k, v biases; attention scores and the
    weighted sum, ``2 * heads * head_dim * context`` each; RMS norms and
    residual adds are left out (under 0.1 % here).  ``head`` adds the
    vocabulary projection, ``2 * hidden * vocab``, for a token whose
    logits are used.
    """
    d = cfg["hidden_size"]
    heads, kv = cfg["num_attention_heads"], cfg["num_key_value_heads"]
    hd = cfg.get("head_dim") or d // heads
    ff = cfg["intermediate_size"]
    weights = d * heads * hd * 2 + d * kv * hd * 2 + 3 * d * ff
    biases = heads * hd + 2 * kv * hd
    attn = 4 * heads * hd * context
    per_layer = 2 * weights + biases + attn
    total = cfg["num_hidden_layers"] * per_layer
    if head:
        total += 2 * d * cfg["vocab_size"]
    return total


def lm_request_flops(cfg: Dict, prompt_len: int, new_tokens: int) -> int:
    """A whole request: every prompt position and every fed-back token
    (``prompt_len + new_tokens - 1`` positions, each attending to all
    positions up to itself), with the vocabulary projection at the
    ``new_tokens`` positions whose logits pick a token."""
    positions = prompt_len + new_tokens - 1
    no_attn = lm_token_flops(cfg, 0, head=False)
    attn_per_key = lm_token_flops(cfg, 1, head=False) - no_attn
    head = lm_token_flops(cfg, 0, head=True) - no_attn
    # sum over positions of the context length 1 + 2 + ... + positions
    return positions * no_attn + attn_per_key * positions * (positions + 1) // 2 + new_tokens * head
