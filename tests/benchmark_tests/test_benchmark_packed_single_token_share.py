"""`attention.packed_single_token_share` (PR 46) on made-up snapshots of
`engine_packed_lanes_total{attention_path}`, with the counter and without it, and
against the stand-in server."""

import pytest
from bench_paths import BENCH  # noqa: F401
from standin import StandIn  # imported here so that conftest's fixture grows it

from kbench import manifest, server

NAME = "attention.packed_single_token_share"


def snap(decode_kernel=None, ragged=None):
    """A scrape with the series given; a series left out is absent."""
    lines = ['engine_kv_context_tokens_total{model_name="bench"} 7']
    for path, n in (("decode_kernel", decode_kernel), ("ragged", ragged)):
        if n is not None:
            lines.append('engine_packed_lanes_total{model_name="bench",'
                         f'attention_path="{path}"}} {n}')
    return server.parse_metrics("\n".join(lines) + "\n")


def read(before, after):
    return manifest.load_reader(NAME).read({"before": before, "after": after})


@pytest.mark.parametrize("before, after, share", [
    # decode-sat: 48 decode lanes and 0.8 prompt chunks a dispatch
    ((4800, 80), (4800 + 48000, 80 + 800), 100 * 48 / 48.8),
    # a decode-only window
    ((0, 0), (960, 0), 100.0),
    # a program that does not split (rings, latent pages, the CPU): its
    # lanes are all the ragged form's, and that reads 0, not nothing
    ((0, 100), (0, 1300), 0.0),
    # chat: a dozen decode lanes beside two chunks
    ((130, 20), (130 + 1300, 20 + 200), 100 * 13 / 15),
    # the counter first seen inside the window
    ((None, None), (480, 20), 96.0),
])
def test_share_of_the_window_s_lanes(before, after, share):
    assert read(snap(*before), snap(*after)) == pytest.approx(share)


@pytest.mark.parametrize("before, after", [
    ((None, None), (None, None)),  # the parent: no such counter
    ((480, 20), (480, 20)),  # no packed step in the window
    ((None, 20), (None, 50)),  # half a counter is no counter
])
def test_nothing_to_read_gives_none_and_does_not_raise(before, after):
    assert read(snap(*before), snap(*after)) is None


def test_reader_matches_its_manifest_entry():
    reader = manifest.load_reader(NAME)
    per_layer = manifest.load_manifest()["per_layer"]
    (entry,) = [m for m in per_layer if m["name"] == NAME]
    assert (entry["layer"], entry["unit"], entry["source"], entry["moves"]) == (
        reader.LAYER, reader.UNIT, reader.SOURCE, reader.MOVES)
    assert (entry["unit"], entry["better"]) == ("%", "higher")
    # the layer's name as the accepted readers of the decode kernel have it
    (walk,) = [m for m in per_layer
               if m["name"] == "attention.decode_null_fetch_share"]
    assert (entry["layer"], entry["moves"]) == (walk["layer"], walk["moves"])
    # every cell runs `mixed` and reports the metric it moves, in the
    # manifest's order; it is the last entry: nothing accepted was moved
    assert entry["workloads"] == [
        w["name"] for w in manifest.load_manifest()["workloads"]]
    assert per_layer[-1] is entry


def test_the_stand_in_s_packed_steps_hold_48_decode_lanes_and_a_chunk():
    with StandIn() as standin:
        before = server.parse_metrics(standin._metrics())
        standin._t0 -= 50.0  # a thousand made-up dispatches later
        after = server.parse_metrics(standin._metrics())
    assert read(before, after) == pytest.approx(100 * 48 / 49)
