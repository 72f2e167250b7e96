"""Operations and bytes of the served work, counted from shapes.

``lm_flops`` counts what the forward pass requires for one token at a
given position: two operations per weight of every matrix it multiplies
by (the embedding lookup multiplies nothing), attention's scores and
weighted sum over the positions it can see, and the selective-state
update of a hybrid layer. Padding rows and recomputation do not count.

``match_bytes`` counts what any exact matcher of one call has to move:
the compiled table's bounds and weights, read once, the checks it is
given and the three numbers it returns per check.
"""
from __future__ import annotations

from bench.lm_ref import layer_runs, leaf_shapes


def n_params(cfg: dict) -> int:
    per_layer = sum(_size(s) for s, _, _ in leaf_shapes(cfg).values())
    return 2 * cfg["vocab"] * cfg["d_model"] + cfg["d_model"] \
        + cfg["n_layers"] * per_layer


def _size(shape) -> int:
    n = 1
    for d in shape:
        n *= d
    return n


def _matrix_weights(cfg: dict) -> int:
    return sum(_size(s) for s, _, kind in leaf_shapes(cfg).values()
               if kind == "dense")


def lm_flops(cfg: dict, position: int) -> float:
    """Operations the forward pass needs for the token at ``position``
    (0-based), which sees ``position + 1`` positions of context."""
    ctx = position + 1
    attn = 0
    for n, window in layer_runs(cfg):
        seen = min(ctx, window) if window > 0 else ctx
        attn += n * 4 * cfg["n_heads"] * cfg["head_dim"] * seen
    ssm = 0
    if cfg.get("parallel_ssm"):
        s = cfg["ssm"]
        di = s["d_inner_mult"] * cfg["d_model"]
        ssm = cfg["n_layers"] * (2 * s["conv_width"] * di
                                 + 7 * di * s["state_dim"])
    mats = cfg["n_layers"] * _matrix_weights(cfg) \
        + cfg["vocab"] * cfg["d_model"]
    return float(2 * mats + attn + ssm)


def sequence_flops(cfg: dict, prompt_len: int, new_tokens: int) -> float:
    """A served request: the prompt and every generated token but the last
    go through the model once each."""
    return sum(lm_flops(cfg, p) for p in range(prompt_len + new_tokens - 1))


def table_bytes(n_rules_padded: int, n_cols: int) -> int:
    """int32 min and max bounds per column and rule, plus the weights."""
    return (2 * n_cols + 1) * n_rules_padded * 4


def match_bytes(n_checks: int, n_rules_padded: int, n_cols: int) -> int:
    return table_bytes(n_rules_padded, n_cols) \
        + n_checks * n_cols * 4 + n_checks * 3 * 4
