"""Bytes and operations a Nemotron-H configuration's Mamba-2 mixers and
held experts need, computed from its sizes (no program code), and which of
a trace's operations are the mixers'.

A Mamba-2 mixer keeps, a lane and layer, a float32 state [heads, head_dim,
state] and the bf16 tail of its convolution [taps - 1, columns], the
columns being x, B and C together.  A routed expert is an UNGATED
feed-forward of two [hidden, width] matrices (kbench/expert_math.py counts
three: gated experts)."""

from .state_math import label_dims

BF16, F32 = 2, 4


def is_nemotron_h(cfg: dict) -> bool:
    return cfg.get("model_type") == "nemotron_h"


def sizes(cfg: dict) -> dict:
    heads, p = cfg["mamba_num_heads"], cfg["mamba_head_dim"]
    groups, n = cfg["n_groups"], cfg["ssm_state_size"]
    return {"H": heads, "P": p, "G": groups, "N": n, "K": cfg["conv_kernel"],
            "inner": heads * p, "conv": heads * p + 2 * groups * n}


def mamba_layers(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"].count("M")


def expert_layers(cfg: dict) -> int:
    return cfg["hybrid_override_pattern"].count("E")


def state_bytes(cfg: dict) -> int:
    """One lane's state and tail in one layer."""
    s = sizes(cfg)
    return s["H"] * s["P"] * s["N"] * F32 + (s["K"] - 1) * s["conv"] * BF16


def update_bytes_per_lane(cfg: dict) -> int:
    """One Mamba-2 layer, one decode step, one lane: what the update cannot
    avoid moving.  The state and the tail are read and written once; the
    step's inputs (the projection's xBC and dt, bf16) and its output (y,
    float32, before the gate) are moved once."""
    s = sizes(cfg)
    return (2 * state_bytes(cfg) + (s["conv"] + s["H"]) * BF16
            + s["inner"] * F32)


def packed_state_pass_bytes(cfg: dict, dispatches: float, seated: float) -> float:
    """The seated lanes' state and tail moved ONCE in every Mamba-2 layer
    of every packed step: what the packed step must read (each slice starts
    from its lane's state) and, once more, what it must write (what each
    lane keeps).  The operations that read it produce chunk shapes
    (`is_chunk_scan`); those that write it produce the state's own shape,
    as the decode step's update does (`is_update`)."""
    return dispatches * mamba_layers(cfg) * seated * state_bytes(cfg)


def scan_bytes_per_token(cfg: dict) -> int:
    """The packed step's scan, one token of one layer: xBC and dt in (bf16),
    y out (float32)."""
    s = sizes(cfg)
    return (s["conv"] + s["H"]) * BF16 + s["inner"] * F32


def scan_flops_per_token(cfg: dict) -> int:
    """The recurrence itself, one token of one layer: the state's update
    (decay and outer product) and its read-out, 2 + 2 operations a state
    element.  A chunked form does other arithmetic; this is what any form
    must at least do."""
    s = sizes(cfg)
    return 4 * s["H"] * s["P"] * s["N"]


def held_expert_bytes(cfg: dict) -> int:
    """One routed expert's weights (up and down): read once for every step
    and layer in which at least one token reached it."""
    return 2 * cfg["hidden_size"] * cfg["moe_intermediate_size"] * BF16


def held_pair_flops(cfg: dict) -> int:
    """One (token, expert) pair through up and down."""
    return 4 * cfg["hidden_size"] * cfg["moe_intermediate_size"]


def is_conv(dims, cfg: dict) -> bool:
    """The convolution's operations, of both steps: arrays over the columns
    it runs over (x, B and C together: a width no other tensor of the model
    has), as [tokens or lanes, columns], the window or tail [lanes, taps or
    taps - 1, columns], or transposed."""
    return sizes(cfg)["conv"] in dims


def is_update(dims, cfg: dict, lanes: int) -> bool:
    """The operations that WRITE the lanes' state, by what they produce: the
    state [lanes, heads, head_dim, state] or the convolution's window or
    tail [lanes, taps or taps - 1, columns].  The decode step's update is
    one such fusion a layer; the packed step's are the selects, the gather
    of each lane's last chunk-end state and the copies that leave what each
    lane keeps.  A label cannot tell the two steps apart (the result's
    shape is the same and a fusion's name is the compiler's), so a share
    read from these seconds counts both steps' bytes
    (`packed_state_pass_bytes`)."""
    s = sizes(cfg)
    return dims == [lanes, s["H"], s["P"], s["N"]] or (
        len(dims) == 3 and dims[0] == lanes and dims[2] == s["conv"]
        and dims[1] in (s["K"], s["K"] - 1))


def is_chunk_scan(dims, cfg: dict, lanes: int) -> bool:
    """The packed step's scan, by what its operations produce: the
    chunk-end states and the pieces of their associative scan [n, heads,
    head_dim, state] (n chunks, not the lanes), arrays over [.., groups,
    heads a group, head_dim, state] (what the chunks' last segments and the
    lanes' windows add), the decay masks [chunks, groups, heads a group, Q,
    Q], the windows and outputs [.., Q, groups, heads a group, head_dim] and
    [tokens, heads, head_dim]."""
    s = sizes(cfg)
    rep = s["H"] // s["G"]
    if len(dims) == 3:
        return dims[1:] == [s["H"], s["P"]]
    if len(dims) == 4:
        return dims[1:] == [s["H"], s["P"], s["N"]] and dims[0] != lanes
    if len(dims) != 5:
        return False
    return (dims[1:] == [s["G"], rep, s["P"], s["N"]]
            or (dims[1:3] == [s["G"], rep] and dims[3] == dims[4])
            or dims[2:] == [s["G"], rep, s["P"]])


def is_ssd(dims, cfg: dict, lanes: int) -> bool:
    """Any operation of the Mamba-2 mixers' convolution, scan or update."""
    return (is_update(dims, cfg, lanes) or is_chunk_scan(dims, cfg, lanes)
            or is_conv(dims, cfg))


def seconds_of(trace: dict, picks) -> float:
    """Device self-seconds of the operations whose result's dimensions
    `picks` accepts."""
    return sum(s for label, s in trace["op_s"].items()
               if picks(label_dims(label) or []))
