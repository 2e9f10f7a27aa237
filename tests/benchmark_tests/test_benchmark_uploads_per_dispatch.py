"""`dispatch.uploads_per_dispatch` (PR 45) on made-up snapshots of
`engine_dispatch_uploads_total` beside `engine_dispatches_total`, with the
counter and without it, and against the stand-in server."""

import pytest
from bench_paths import BENCH  # noqa: F401
from standin import StandIn  # imported here so that conftest's fixture grows it

from kbench import manifest, server

NAME = "dispatch.uploads_per_dispatch"


def snap(dispatches=None, uploads=None):
    """A scrape with the series given; a series left out is absent."""
    lines = ['engine_kv_context_tokens_total{model_name="bench"} 7']
    for program, n in (dispatches or {}).items():
        lines.append('engine_dispatches_total{model_name="bench",'
                     f'program="{program}"}} {n}')
    if uploads is not None:
        lines.append(f'engine_dispatch_uploads_total{{model_name="bench"}} {uploads}')
    return server.parse_metrics("\n".join(lines) + "\n")


def read(before, after):
    return manifest.load_reader(NAME).read({"before": before, "after": after})


@pytest.mark.parametrize("before, after, uploads", [
    # 400 mixed dispatches in the window, three packed buffers each
    ((dict(mixed=100), 300), (dict(mixed=500), 1500), 3.0),
    # someone gave the launch a fourth argument of its own
    ((dict(mixed=100), 300), (dict(mixed=200), 700), 4.0),
    # a window that holds dense-path launches too: over all the dispatches
    ((dict(mixed=10, mixed_decode=0), 30),
     (dict(mixed=20, mixed_decode=10), 30 + 30 + 80), 5.5),
    # the counter first seen inside the window
    ((dict(mixed=0), None), (dict(mixed=50), 150), 3.0),
    # dispatches that uploaded nothing read 0, not nothing
    ((dict(mixed=5), 15), (dict(mixed=9), 15), 0.0),
])
def test_uploads_of_the_window_over_its_dispatches(before, after, uploads):
    assert read(snap(*before), snap(*after)) == pytest.approx(uploads)


@pytest.mark.parametrize("before, after", [
    # the parent: dispatches, and no such counter
    ((dict(mixed=100), None), (dict(mixed=500), None)),
    ((None, None), (None, None)),  # nothing at all
    # no dispatch in the window
    ((dict(mixed=100), 300), (dict(mixed=100), 300)),
])
def test_nothing_to_read_gives_none_and_does_not_raise(before, after):
    assert read(snap(*before), snap(*after)) is None


def test_reader_matches_its_manifest_entry():
    reader = manifest.load_reader(NAME)
    per_layer = manifest.load_manifest()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == NAME]
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES)
    assert (entry["unit"], entry["better"]) == ("count", "lower")
    # the layer's name as the accepted readers of a dispatch's parts have it
    (upload,) = [m for m in per_layer if m["name"] == "dispatch.upload_ms"]
    assert (entry["layer"], entry["moves"]) == (upload["layer"], upload["moves"])
    # every cell runs `mixed` and reports the metric it moves, in the
    # manifest's order
    assert entry["workloads"] == [
        w["name"] for w in manifest.load_manifest()["workloads"]]


def test_the_stand_in_s_dispatches_upload_three_buffers():
    with StandIn() as standin:
        before = server.parse_metrics(standin._metrics())
        standin._t0 -= 50.0  # a thousand made-up dispatches later
        after = server.parse_metrics(standin._metrics())
    assert read(before, after) == pytest.approx(3.0)
