"""Bytes and operations a configuration's routed experts need, computed
from its sizes (no program code), and which of a trace's operations are
theirs.  An expert is a gated feed-forward of three [hidden, width]
matrices; a routed (token, expert) pair multiplies one row through them."""

from .state_math import label_dims

BF16 = 2

#: the grouped matmul (`jax.lax.ragged_dot` on the TPU) and its metadata
GROUPED_MATMUL = "ragged-dot"


def has_experts(cfg: dict) -> bool:
    return bool(cfg.get("n_routed_experts"))


def expert_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - cfg.get("first_k_dense_replace", 0)


def expert_bytes(cfg: dict) -> int:
    """One routed expert's weights: read once for every step and layer in
    which at least one token reached it."""
    return 3 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * BF16


def pair_flops(cfg: dict) -> int:
    """One (token, expert) pair through gate, up and down."""
    return 6 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def is_grouped_matmul(label: str) -> bool:
    return label.startswith(GROUPED_MATMUL)


def grouped_matmul_seconds(trace: dict) -> float:
    """Device self-seconds of the grouped matmuls in a reduced trace."""
    return sum(s for label, s in trace["op_s"].items()
               if is_grouped_matmul(label))


def pair_rows(flags: dict, policy: dict, cfg: dict) -> set:
    """Leading dimensions an array of (token, expert) pairs can have: the
    experts a token x the lengths a forward step's buffer can take (the
    lanes of a decode step; the token buckets up to max_prefill_len of a
    packed step), less those that are a buffer length themselves (4 x 512 =
    2048: such an array cannot be told from a packed step's own)."""
    k = cfg["num_experts_per_tok"]
    cap = flags["max_prefill_len"]
    buffers = {flags["max_batch_size"], cap} | {
        t for t in policy["token_buckets"] if t <= cap}
    return {k * t for t in buffers} - buffers


def is_routing_op(label: str, rows: set, cfg: dict) -> bool:
    """The order / gather / scatter operations around the grouped matmuls,
    told by what they produce: arrays over the (token, expert) pairs,
    [pairs], [pairs, experts + 1], [pairs, hidden] or [pairs, width], or the
    same unflattened [tokens, experts a token, hidden]."""
    dims = label_dims(label)
    if not dims:
        return False
    k, e = cfg["num_experts_per_tok"], cfg["n_routed_experts"]
    widths = (cfg["hidden_size"], cfg["moe_intermediate_size"], e + 1)
    if len(dims) == 1:
        return dims[0] in rows
    if len(dims) == 2:
        return dims[0] in rows and dims[1] in widths
    return (len(dims) == 3 and dims[1] == k and dims[0] * k in rows
            and dims[2] == cfg["hidden_size"])


def routing_seconds(trace: dict, flags: dict, policy: dict, cfg: dict) -> float:
    """Device self-seconds of the operations around the grouped matmuls."""
    rows = pair_rows(flags, policy, cfg)
    return sum(s for label, s in trace["op_s"].items()
               if not is_grouped_matmul(label)
               and is_routing_op(label, rows, cfg))
