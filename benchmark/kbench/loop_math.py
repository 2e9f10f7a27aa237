"""Bytes a looped configuration's forward step must stream and its decode
attention must read, computed from the configuration's sizes (no program
code).  A looped model (`total_ut_steps` passes over ONE set of layers)
streams its layers once a pass and keeps K/V rows of its own for every
(pass, layer)."""

BF16 = 2  # bytes: the dtype every configuration of the benchmark runs in


def passes(cfg: dict) -> int:
    return int(cfg.get("total_ut_steps", 1))


def layer_params(cfg: dict) -> int:
    """One decoder layer: the four attention projections, the gated
    feed-forward's three, and its norms (two, or four with sandwich norms
    as `model_type: ouro` has them)."""
    h = cfg["hidden_size"]
    nq = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads", nq)
    d = cfg.get("head_dim") or h // nq
    norms = 4 if cfg.get("model_type") == "ouro" else 2
    return (h * (nq + 2 * nkv) * d + nq * d * h
            + 3 * h * cfg["intermediate_size"] + norms * h)


def stack_bytes(cfg: dict) -> int:
    """The layers' weights, streamed once a PASS."""
    return cfg["num_hidden_layers"] * layer_params(cfg) * BF16


def head_bytes(cfg: dict) -> int:
    """The output head (the tied embedding serves as one), once a STEP; the
    embedding's gathered rows and the final norm are left out (KBs)."""
    return cfg["vocab_size"] * cfg["hidden_size"] * BF16


def step_stream_bytes(cfg: dict) -> int:
    """What one forward step cannot avoid reading from HBM whatever its
    batch: every pass streams the whole stack (5 GB of layers do not stay
    on the chip between passes), the head is read once."""
    return passes(cfg) * stack_bytes(cfg) + head_bytes(cfg)


def cache_rows(cfg: dict) -> int:
    """K/V rows a token holds: one per (pass, layer)."""
    return passes(cfg) * cfg["num_hidden_layers"]


def row_token_bytes(cfg: dict) -> int:
    """K and V of one token in one row."""
    nq = cfg["num_attention_heads"]
    nkv = cfg.get("num_key_value_heads", nq)
    d = cfg.get("head_dim") or cfg["hidden_size"] // nq
    return 2 * nkv * d * BF16


def context_read_bytes(cfg: dict, context_tokens: float) -> float:
    """Bytes decode attention must read for `context_tokens` (the sum over
    decode steps of the live lanes' cached tokens): each in every row."""
    return context_tokens * row_token_bytes(cfg) * cache_rows(cfg)


def has_series(snapshot: dict, name: str) -> bool:
    """Whether the program publishes `name` at all (a counter that reads 0
    is there; a program from before it is not)."""
    return any(series == name for series, _ in snapshot)
