"""Bytes a hybrid configuration's per-lane state holds and its state update
must move, computed from the configuration's sizes (no program code)."""

import re

#: an operation's label ends in its (first) result's dimensions
_DIMS = re.compile(r"_[a-z]+\d*_((?:\d+_)+)$")


def label_dims(label: str):
    m = _DIMS.search(label)
    return [int(d) for d in m.group(1).split("_") if d] if m else None


def share_of_labels(trace, picks):
    """Percent of device busy time in the operations whose label `picks`
    accepts; None without a trace or where none ran."""
    if not trace or not trace.get("busy_s"):
        return None
    picked = [s for label, s in trace["op_s"].items() if picks(label)]
    return 100.0 * sum(picked) / trace["busy_s"] if picked else None


def mamba_layers(cfg: dict) -> int:
    """Layers whose mixer is a Mamba mixer: every other layer of the
    self-decoder (the first half) and the one that opens the second."""
    return cfg["num_hidden_layers"] // 4 + 1


def window_layers(cfg: dict) -> int:
    return cfg["num_hidden_layers"] // 4


def update_bytes_per_call(cfg: dict, lanes: int) -> int:
    """One Mamba layer, one decode step, `lanes` lanes: what the state
    update cannot avoid moving.  The scan's float32 state and the
    convolution's bf16 tail are read and written once; the step's inputs
    (x and z from the input projection, bf16; dt, the convolution's output
    and the scan's output, which the projections around it produce and
    consume in float32; B and C) and its output are moved once."""
    di, n = cfg["mamba_d_inner"], cfg["mamba_d_state"]
    taps = cfg["mamba_d_conv"]
    state = 2 * di * n * 4 + 2 * (taps - 1) * di * 2
    inputs = di * 2 + di * 4 + 2 * n * 4  # x (bf16), dt (f32), B and C (f32)
    output = di * 4  # y (f32), before the gate
    return lanes * (state + inputs + output)


def is_state_update(dims, cfg: dict, lanes: int) -> bool:
    """The decode step's scan and convolution operations, by what they
    produce: the state [lanes, d_inner, d_state] or the convolution's
    window / tail [lanes, d_conv or d_conv - 1, d_inner]."""
    di, n, taps = cfg["mamba_d_inner"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    return dims in ([lanes, di, n], [lanes, taps, di], [lanes, taps - 1, di])


def is_ssm(dims, cfg: dict) -> bool:
    """Any scan or convolution operation, of the decode step or of the
    packed buffer: results whose last two dimensions are the state's
    (d_inner, d_state) or the convolution window's (taps, d_inner)."""
    di, n, taps = cfg["mamba_d_inner"], cfg["mamba_d_state"], cfg["mamba_d_conv"]
    return len(dims) >= 3 and (
        dims[-2:] == [di, n] or dims[-2:] in ([taps, di], [taps - 1, di]))
