"""`dispatch.padded_share` (PR 33) on made-up snapshots of
`engine_dispatch_shape_total`, and against the stand-in server."""

import pytest
from bench_paths import BENCH  # noqa: F401

from kbench import manifest, server

NAME = "dispatch.padded_share"


def snap(**fits):
    lines = [f'engine_dispatch_shape_total{{model_name="bench",fit="{fit}"}} {n}'
             for fit, n in fits.items()]
    lines.append('engine_dispatches_total{model_name="bench",program="mixed"} 7')
    return server.parse_metrics("\n".join(lines) + "\n")


@pytest.mark.parametrize("before, after, share", [
    # 100 dispatches in the window, 4 of them padded
    (dict(exact=50, padded=1, compiled=12), dict(exact=146, padded=5, compiled=12), 4.0),
    # a closed loop whose pairs are all loaded
    (dict(exact=10, padded=0, compiled=13), dict(exact=110, padded=0, compiled=13), 0.0),
    # a compile in the window counts among the dispatches, not the padded
    (dict(exact=0, padded=0, compiled=3), dict(exact=6, padded=3, compiled=4), 30.0),
    # the labels first seen inside the window
    (dict(compiled=2), dict(exact=1, padded=1, compiled=2), 50.0),
])
def test_share_of_the_window_s_mixed_dispatches(before, after, share):
    run = {"before": snap(**before), "after": snap(**after)}
    assert manifest.load_reader(NAME).read(run) == pytest.approx(share)


@pytest.mark.parametrize("before, after", [
    ({}, {}),  # the parent: no such counter
    (dict(exact=5, padded=1), dict(exact=5, padded=1)),  # no dispatch in the window
])
def test_nothing_to_read_gives_none_and_does_not_raise(before, after):
    run = {"before": snap(**before), "after": snap(**after)}
    assert manifest.load_reader(NAME).read(run) is None


def test_reader_matches_its_manifest_entry():
    reader = manifest.load_reader(NAME)
    (entry,) = [m for m in manifest.load_manifest()["per_layer"]
                if m["name"] == NAME]
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES)
    assert entry["better"] == "lower"
    # every cell reports the end-to-end metric it moves and runs `mixed`
    cells = {w["name"] for w in manifest.load_manifest()["workloads"]}
    assert set(entry["workloads"]) == cells


def test_the_stand_in_pads_one_dispatch_in_ten():
    from standin import StandIn

    with StandIn() as standin:
        before = server.parse_metrics(standin._metrics())
        standin._t0 -= 50.0  # a thousand made-up dispatches later
        after = server.parse_metrics(standin._metrics())
    share = manifest.load_reader(NAME).read({"before": before, "after": after})
    assert share == pytest.approx(10.0, abs=0.2)
