"""The paged_pages_streamed_share reader on synthetic tick logs."""
import types

import pytest

from bench import spec


def _ctx(ticks):
    return types.SimpleNamespace(ticks=ticks)


def test_share_of_table_pages_streamed():
    read = spec.reader("paged_pages_streamed_share")
    ticks = [(0.0, 0.1, {"kv_pages": 19, "kv_pages_table": 64}),
             (0.1, 0.1, {"kv_pages": 0, "kv_pages_table": 0}),
             (0.2, 0.1, {"kv_pages": 13, "kv_pages_table": 64})]
    assert read(_ctx(ticks)) == pytest.approx(100.0 * 32 / 128)


@pytest.mark.parametrize("ticks", [
    [],
    [(0.0, 0.1, {"wait_s": 0.05})],
    [(0.0, 0.1, {"kv_pages": 3, "kv_pages_table": 8}),
     (0.1, 0.1, {"wait_s": 0.05})],
    [(0.0, 0.1, {"kv_pages": 0, "kv_pages_table": 0})],
])
def test_no_reading_without_the_counts(ticks):
    """A tick log without the counts (a program that lacks them) or
    without a Pallas decode dispatch reads nothing."""
    assert spec.reader("paged_pages_streamed_share")(_ctx(ticks)) is None
