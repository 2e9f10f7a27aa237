"""Bytes and operations a configuration's Kimi-delta mixers (the gated
delta rule with a decay per channel, arXiv:2510.26692) need, computed from
its sizes (no program code), and which of a trace's operations are theirs.

A mixer keeps, a lane and layer, a float32 state [heads, head_dim,
head_dim] and the bf16 tail of its convolution [taps - 1, columns], the
columns being q, k and v together.  Every count here is a floor: what any
implementation of the recurrence must move or multiply, not what the
program's chunked form happens to do, so that a share of a peak cannot
read over 100 % now or after a kernel replaces the XLA form."""

import re

BF16, F32 = 2, 4

#: an operation's label ends in its (first) result's type and dimensions
_TYPED = re.compile(r"_([a-z]+\d*)_((?:\d+_)+)$")


def label_shape(label: str):
    """(`f32`, [48, 64, 128]) of `multiply_reduce_fusion_f32_48_64_128_`;
    (None, []) of a label that does not end so."""
    m = _TYPED.search(label)
    if not m:
        return None, []
    return m.group(1), [int(d) for d in m.group(2).split("_") if d]

#: tokens a piece of the program's packed form holds, and its sub-block
#: (kserve_tpu/ops/delta.py KDA_CHUNK / KDA_SUB: copied, as a configuration's
#: `engine_policy` copies the packing policy): they tell which operations
#: are the chunked form's, and enter no count
CHUNK, SUB = 64, 16


def is_solar_open2(cfg: dict) -> bool:
    return cfg.get("model_type") == "solar_open2" and bool(
        cfg.get("linear_attn_config"))


def sizes(cfg: dict) -> dict:
    linear = cfg["linear_attn_config"]
    heads, d = linear["num_heads"], linear["head_dim"]
    return {"H": heads, "d": d, "K": linear["short_conv_kernel_size"],
            "inner": heads * d, "conv": 3 * heads * d}


def kda_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] - len(cfg["gqa_layers"])


def state_bytes(cfg: dict) -> int:
    """One lane's state and tail in one layer."""
    s = sizes(cfg)
    return s["H"] * s["d"] * s["d"] * F32 + (s["K"] - 1) * s["conv"] * BF16


def token_bytes(cfg: dict) -> int:
    """One token of one layer through the recurrence, whichever form: q, k
    and v in (the projection's bf16 columns), the decay a head and channel
    and the step size a head in (bf16), the output out (float32, before the
    gated norm)."""
    s = sizes(cfg)
    return (s["conv"] + s["inner"] + s["H"]) * BF16 + s["inner"] * F32


def update_bytes_per_lane(cfg: dict) -> int:
    """One layer, one decode step, one lane: what the update cannot avoid
    moving.  The state and the tail are read and written once; the step's
    inputs and its output are moved once."""
    return 2 * state_bytes(cfg) + token_bytes(cfg)


def packed_state_pass_bytes(cfg: dict, dispatches: float, seated: float) -> float:
    """The seated lanes' state and tail moved ONCE in every Kimi-delta layer
    of every packed step: what the packed step must read (each slice starts
    from its lane's state) and, once more, what it must write (what each
    lane keeps).  The write leaves operations of the state's own shape, as
    the decode step's update does (`is_update`); the read is the chunked
    form's (`is_chunk`)."""
    return dispatches * kda_layers(cfg) * seated * state_bytes(cfg)


def chunk_flops_per_token(cfg: dict) -> int:
    """The recurrence itself, one token of one layer: the three contractions
    with the state that every form has (what the state has seen of the key,
    what the token adds, what the query reads), 2 operations a state
    element each.  A chunked form does other arithmetic inside its chunk;
    this is what any form must at least do."""
    s = sizes(cfg)
    return 6 * s["H"] * s["d"] * s["d"]


def is_conv(dims, cfg: dict) -> bool:
    """The convolution's operations, of both steps: arrays over the columns
    it runs over (q, k and v together: a width no other tensor of the model
    has).  The projection that makes those columns produces an array of
    that width too and is counted with them (a matmul of 4 ms a layer at
    4096 tokens)."""
    return sizes(cfg)["conv"] in dims


def is_update(dims, cfg: dict, lanes: int, dtype: str = "") -> bool:
    """The operations of the one-step update, by what they produce: the
    state [lanes, heads, head_dim, head_dim], the convolution's window or
    tail [lanes, taps or taps - 1, columns], and the float32 arrays a lane
    and head [lanes, heads, head_dim] (what the state has seen of the key
    and what the query reads are two contractions of it of that shape; the
    attention row's arrays of those dimensions are bf16, and its kernel is
    named).  The decode step's update is two or three such fusions a layer;
    the packed step's are the one-step update of its single-token lanes,
    the writes of a piece's last state and the select that leaves what each
    lane keeps.  A label cannot tell the two steps apart, so a share read
    from these seconds counts both steps' bytes
    (`packed_state_pass_bytes`)."""
    s = sizes(cfg)
    return dims == [lanes, s["H"], s["d"], s["d"]] or (
        dims == [lanes, s["H"], s["d"]] and dtype == "f32") or (
        len(dims) == 3 and dims[0] == lanes and dims[2] == s["conv"]
        and dims[1] in (s["K"], s["K"] - 1))


def is_chunk(dims, cfg: dict, lanes: int, dtype: str = "") -> bool:
    """The packed step's chunked form, by what its operations produce: one
    state [.., heads, head_dim, head_dim] (not the lanes'), the float32
    buffers a piece's window is cut from and its output written to [tokens,
    heads, head_dim] (q, k and v as the recurrence takes them, the decay,
    the output: the attention row's buffers of those dimensions are bf16),
    and the float32 arrays of one piece, whose every dimension is one of
    the piece's own sizes (heads, head_dim or twice it, and the powers of
    two up to the chunk: its sub-block, their quotient, and the block sizes
    of the triangular system's inverse by halves) with the heads among
    them: decayed scores [2, heads, Q, Q], the system's solution [heads, Q,
    2 head_dim], blocks of differences [Q / sub, sub, sub, heads,
    head_dim], a level of the inverse [heads, Q / 2 s, 2 s, 2 s].  At the
    published sizes heads = CHUNK = 64, so a float32 [64, 64, 128] could
    also be a 64-token dispatch's array a head: a buffer no dispatch of a
    saturated window has."""
    s = sizes(cfg)
    H, d = s["H"], s["d"]
    if len(dims) < 3:
        return False
    if dims[-3:] == [H, d, d]:
        return dims[0] != lanes or len(dims) == 3
    if dtype != "f32":
        return False
    if len(dims) == 3 and dims[1:] == [H, d] and dims[0] > max(CHUNK, lanes):
        return True
    own = {H, d, 2 * d} | {1 << i for i in range(CHUNK.bit_length())}
    return H in dims and set(dims) <= own


def is_kda(dims, cfg: dict, lanes: int, dtype: str = "") -> bool:
    """Any operation of the mixers' convolution, update or chunked form."""
    return (is_update(dims, cfg, lanes, dtype)
            or is_chunk(dims, cfg, lanes, dtype) or is_conv(dims, cfg))


def seconds_of(trace: dict, picks) -> float:
    """Device self-seconds of the operations whose result's dimensions and
    type `picks(dims, dtype)` accepts."""
    total = 0.0
    for label, seconds in trace["op_s"].items():
        dtype, dims = label_shape(label)
        if picks(dims, dtype):
            total += seconds
    return total
