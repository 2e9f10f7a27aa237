"""`attention.decode_null_fetch_share` (PR 42) on made-up snapshots of
`engine_kv_decode_pages_total{reach}`, and against the stand-in server."""

import pytest
from bench_paths import BENCH  # noqa: F401
from standin import StandIn  # imported here so that conftest's fixture grows it

from kbench import manifest, server

NAME = "attention.decode_null_fetch_share"


def snap(**reaches):
    lines = [f'engine_kv_decode_pages_total{{model_name="bench",reach="{reach}"}} {n}'
             for reach, n in reaches.items()]
    lines.append('engine_kv_context_tokens_total{model_name="bench"} 7')
    return server.parse_metrics("\n".join(lines) + "\n")


@pytest.mark.parametrize("before, after, share", [
    # decode-sat dealt in lane order, as sampled: a lane holds 18.9 pages, the longest of eight 30.3
    (dict(own=1000, block=2000), dict(own=1000 + 189_000, block=2000 + 303_000),
     100 * (1 - 189 / 303)),
    # every lane of every block equally long: nothing lies past a lane's own
    (dict(own=640, block=640), dict(own=6400, block=6400), 0.0),
    # one lane of eight live: seven eighths of the walk belong to nobody
    (dict(own=0, block=0), dict(own=500, block=4000), 87.5),
    # the labels first seen inside the window
    ({}, dict(own=300, block=400), 25.0),
])
def test_share_of_the_walk_past_the_lanes_own_pages(before, after, share):
    run = {"before": snap(**before), "after": snap(**after)}
    assert manifest.load_reader(NAME).read(run) == pytest.approx(share)


@pytest.mark.parametrize("before, after", [
    ({}, {}),  # the parent: no such counter
    (dict(own=50, block=80), dict(own=50, block=80)),  # no decode step in the window
])
def test_nothing_to_read_gives_none_and_does_not_raise(before, after):
    run = {"before": snap(**before), "after": snap(**after)}
    assert manifest.load_reader(NAME).read(run) is None


def test_reader_matches_its_manifest_entry():
    reader = manifest.load_reader(NAME)
    per_layer = manifest.load_manifest()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == NAME]
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES)
    assert entry["better"] == "lower" and entry is per_layer[-1]
    # the layer's name as the accepted kernels' metrics have it
    (roofline,) = [m for m in per_layer if m["name"] == "attention.decode_roofline"]
    assert entry["layer"] == roofline["layer"]
    # every cell decodes through the kernel and reports the metric it moves
    cells = [w["name"] for w in manifest.load_manifest()["workloads"]]
    assert entry["workloads"] == cells


def test_the_stand_in_s_lanes_own_three_quarters_of_their_walk():
    with StandIn() as standin:
        before = server.parse_metrics(standin._metrics())
        standin._t0 -= 50.0  # a thousand made-up dispatches later
        after = server.parse_metrics(standin._metrics())
    share = manifest.load_reader(NAME).read({"before": before, "after": after})
    assert share == pytest.approx(25.0)
