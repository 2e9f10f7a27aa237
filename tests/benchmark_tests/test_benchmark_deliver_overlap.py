"""`dispatch.deliver_overlap_share` (PR 36) on made-up snapshots of
`engine_dispatch_deliveries_total`, and against the stand-in server."""

import pytest
from bench_paths import BENCH  # noqa: F401

from kbench import manifest, server
from standin import StandIn  # imported here so that conftest's fixture grows it

NAME = "dispatch.deliver_overlap_share"


def snap(**whens):
    lines = [f'engine_dispatch_deliveries_total{{model_name="bench",when="{when}"}} {n}'
             for when, n in whens.items()]
    lines.append('engine_dispatches_total{model_name="bench",program="mixed"} 7')
    lines.append('engine_dispatch_deliver_seconds_total{model_name="bench"} 1.5')
    return server.parse_metrics("\n".join(lines) + "\n")


@pytest.mark.parametrize("before, after, share", [
    # every token of the window behind the next launch
    (dict(overlapped=40, inline=3), dict(overlapped=140, inline=3), 100.0),
    # every one in place (each lane carries a stop string, or the legacy path)
    (dict(overlapped=40, inline=3), dict(overlapped=40, inline=103), 0.0),
    # one lane in four carries a stop string
    (dict(overlapped=10, inline=10), dict(overlapped=85, inline=35), 75.0),
    # the labels first seen inside the window
    (dict(), dict(overlapped=9, inline=1), 90.0),
])
def test_share_of_the_window_s_deliveries(before, after, share):
    run = {"before": snap(**before), "after": snap(**after)}
    assert manifest.load_reader(NAME).read(run) == pytest.approx(share)


@pytest.mark.parametrize("before, after", [
    ({}, {}),  # the parent: no such counter
    (dict(overlapped=5, inline=1), dict(overlapped=5, inline=1)),  # no delivery in the window
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
    assert entry["better"] == "higher"
    # every cell reports the end-to-end metric it moves and runs `mixed`
    cells = {w["name"] for w in manifest.load_manifest()["workloads"]}
    assert set(entry["workloads"]) == cells


def test_the_stand_in_delivers_behind_the_launch():
    with StandIn() as standin:
        before = server.parse_metrics(standin._metrics())
        standin._t0 -= 50.0  # a thousand made-up dispatches later
        after = server.parse_metrics(standin._metrics())
    share = manifest.load_reader(NAME).read({"before": before, "after": after})
    assert share == pytest.approx(100.0)
