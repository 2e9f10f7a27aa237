"""Bytes and operations a Cohere-2 MoE configuration's window attention and
held experts need, computed from its sizes (no program code).

A window layer (`layer_types: sliding_attention`) keeps, a lane, a ring of
`sliding_window` tokens of K and V.  In a packed step a slice of n tokens
that starts at position s multiplies each query with the keys inside its
window (`pairs`: the query at offset j sees min(s + j + 1, window) keys)
and cannot avoid reading the ring tokens its first query sees plus its own
(`keys`), its queries and writing its outputs; the program counts the three
at launch (`engine_window_ragged_work_total{unit}`, summed over the window
layers).  A routed expert is a GATED feed-forward of three [hidden, width]
matrices, the width being this family's `intermediate_size`."""

BF16 = 2

#: the packed step's window attention kernel, as a trace names its calls
WINDOW_RAGGED_KERNEL = "window_attention_ragged"


def is_cohere2_moe(cfg: dict) -> bool:
    return cfg.get("model_type") == "cohere2_moe"


def window_layers(cfg: dict) -> int:
    return sum(kind == "sliding_attention" for kind in cfg["layer_types"])


def kv_token_bytes(cfg: dict) -> int:
    """K and V of one token in one window layer."""
    return 2 * cfg["num_key_value_heads"] * cfg["head_dim"] * BF16


def ring_bytes_per_lane(cfg: dict) -> int:
    """What one lane's rings hold, whatever its context."""
    return window_layers(cfg) * cfg["sliding_window"] * kv_token_bytes(cfg)


def window_ragged_flops(cfg: dict, pairs: float) -> float:
    """(query, key) pairs, each through q.k and p.v for every query head."""
    return pairs * cfg["num_attention_heads"] * cfg["head_dim"] * 4


def window_ragged_bytes(cfg: dict, keys: float, queries: float) -> float:
    """K/V rows read once, queries read and outputs written once."""
    return (keys * kv_token_bytes(cfg)
            + queries * 2 * cfg["num_attention_heads"] * cfg["head_dim"] * BF16)


def kernel_seconds(trace: dict) -> float:
    """Device self-seconds of the window kernel in a reduced trace."""
    return sum(s for label, s in trace["op_s"].items()
               if label.startswith(WINDOW_RAGGED_KERNEL))


def held_expert_bytes(cfg: dict) -> int:
    """One routed expert's weights (gate, up and down): read once for every
    step and layer in which at least one token reached it."""
    return 3 * cfg["hidden_size"] * cfg["intermediate_size"] * BF16


def held_pair_flops(cfg: dict) -> int:
    """One (token, expert) pair through gate, up and down."""
    return 6 * cfg["hidden_size"] * cfg["intermediate_size"]
