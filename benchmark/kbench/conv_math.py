"""Bytes a configuration's gated short convolutions (the LFM2 family's
`Lfm2ShortConv`: `[B | C | x] = u W_in`, `z = B * x`, a depthwise causal
convolution of a few taps over z, `y = C * conv`) need, computed from its
sizes (no program code), and which of a trace's operations are theirs.

A mixer keeps, a lane and layer, the bf16 tail of its convolution [taps - 1,
hidden] and nothing else.  Every count here is a floor: what any
implementation must move, not what the program's XLA form happens to, so
that a share of a peak cannot read over 100 % now or after a kernel
replaces that form."""

from .delta_math import label_shape, seconds_of  # noqa: F401  (the readers' and the tests')

BF16 = 2


def is_lfm2_moe(cfg: dict) -> bool:
    return cfg.get("model_type") == "lfm2_moe" and "conv" in (
        cfg.get("layer_types") or ())


def sizes(cfg: dict) -> dict:
    h = cfg["hidden_size"]
    return {"hidden": h, "in": 3 * h, "K": int(cfg.get("conv_L_cache", 3))}


def conv_layers(cfg: dict) -> int:
    return sum(kind == "conv" for kind in cfg["layer_types"])


def tail_bytes(cfg: dict) -> int:
    """One lane's tail in one layer."""
    s = sizes(cfg)
    return (s["K"] - 1) * s["hidden"] * BF16


def taps_token_bytes(cfg: dict) -> int:
    """One token of one layer through the first product and the taps: B and
    x in (the projection's bf16 columns), the convolution's row out at 2 B
    a value.  What lies between them (z = B * x) need never leave the chip's
    fast memory.  The second product (C in, y out: 2 x hidden x 2 B more) is
    not in it: XLA fuses it into the output projection, so that its seconds
    are the projection's (`is_packed_taps`)."""
    return 3 * sizes(cfg)["hidden"] * BF16


def packed_bytes(cfg: dict, layer_tokens: float, slices: float) -> float:
    """`layer_tokens` (packed tokens x short-conv layers) through the first
    product and the taps, and the tail of each of the `slices` met read once
    and written once in every short-conv layer."""
    return (layer_tokens * taps_token_bytes(cfg)
            + slices * conv_layers(cfg) * 2 * tail_bytes(cfg))


def is_in_proj(dims, cfg: dict) -> bool:
    """The input projection's result: rows of B, C and x together, a width
    (3 x hidden) no other tensor of the model has."""
    return len(dims) == 2 and dims[-1] == sizes(cfg)["in"]


def is_tail(dims, cfg: dict, lanes: int) -> bool:
    """The lanes' tails, the windows cut from them and the rows gathered
    for them: [lanes, 1 .. taps, hidden], or the lanes' taps - 1 rows as one
    axis [lanes x (taps - 1), hidden] (no dispatch has that many tokens:
    the ladder's rungs are powers of two)."""
    s = sizes(cfg)
    if len(dims) == 3:
        return (dims[0] == lanes and dims[2] == s["hidden"]
                and 1 <= dims[1] <= s["K"])
    return dims == [lanes * (s["K"] - 1), s["hidden"]]


def is_taps(dims, dtype: str, cfg: dict, lanes: int, cap: int) -> bool:
    """The first product and the taps, by what they produce: the tails, and
    the convolution's float32 rows: [tokens, hidden] in the packed step,
    `tokens` a rung of the ladder (a power of two up to `cap`, the server's
    max_prefill_len), [hidden, lanes] in the decode steps.  The model's
    other arrays of those dimensions are bf16 (norms, residuals,
    projections), but for ONE: the routed experts gather their float32 rows
    as [tokens x experts a token, hidden], which is [a rung, hidden] too
    where a dispatch of a quarter of `cap` or less runs.  A cell whose
    window holds such dispatches reads them as the convolution's: its share
    then reads high and its roofline low, never over."""
    h = sizes(cfg)["hidden"]
    if is_tail(dims, cfg, lanes):
        return True
    if dtype != "f32" or len(dims) != 2:
        return False
    rung = dims[0] & (dims[0] - 1) == 0 and lanes < dims[0] <= cap
    return dims == [h, lanes] or (dims[1] == h and rung)


def is_packed_taps(dims, dtype: str, cfg: dict, lanes: int, cap: int) -> bool:
    """`is_taps` without the decode steps' rows of one token a lane."""
    return is_taps(dims, dtype, cfg, lanes, cap) and dims != [
        sizes(cfg)["hidden"], lanes]
