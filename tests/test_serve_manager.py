"""``serve.GroupedKVManager``: one ``PagedKVManager`` a cache group, gated
together.  A window group's slot holds its ring's pages however long the
request; retirement returns every group's pages; what a ring cannot carry
raises an error that names the kind."""
import numpy as np
import pytest

from mxnet_tpu.base import MXNetError
from mxnet_tpu.serve import CacheGroup, GroupedKVManager, PagedKVManager

PAGE, SLOTS = 4, 3
GROUPS = [CacheGroup("full", 64, (0, 5)), CacheGroup("window", 16,
                                                     (1, 2, 3, 4, 6))]


def manager(pool_pages=0):
    return GroupedKVManager(SLOTS, GROUPS, PAGE, pool_pages=pool_pages)


def test_one_manager_a_group_each_with_its_own_pages_and_tables():
    mgr = manager()
    full, window = mgr.groups
    assert (full.kind, window.kind) == ("full", "window")
    assert (full.name, window.name) == ("full", "window")
    assert (full.pages_per_slot, window.pages_per_slot) == (16, 4)
    assert (full.pool_pages, window.pool_pages) == (3 * 16 + 1, 3 * 4 + 1)
    assert full.tables.shape == (3, 16) and window.tables.shape == (3, 4)
    assert mgr.pool_pages == full.pool_pages + window.pool_pages
    assert mgr.prefix_cache is None and full.prefix_cache is None
    assert full.allocator is not window.allocator
    assert mgr.allocator is full.allocator
    # a single PagedKVManager is its own only group
    one = PagedKVManager(SLOTS, 64, PAGE)
    assert one.groups == [one] and one.kind == "full"


@pytest.mark.parametrize("length", [5, 16, 17, 40, 64])
def test_window_pages_a_slot_are_bounded_by_the_ring(length):
    mgr = manager()
    full, window = mgr.groups
    gate = mgr.gate(np.arange(8), 8, length - 8)
    assert gate is not None and gate[0] == 0 and gate[1] == []
    mgr.map_slot(0, gate[1], gate[2])
    v0 = mgr.version
    for pos in range(0, length, 8):         # chunks of 8, then nothing more
        assert mgr.ensure(0, pos, min(pos + 8, length)) == []
    assert full.slot_page_count(0) == -(-length // PAGE)
    assert window.slot_page_count(0) == min(-(-length // PAGE), 4)
    assert mgr.slot_page_count(0) == full.slot_page_count(0) \
        + window.slot_page_count(0)
    assert mgr.version > v0
    # past the ring, a window slot recycles its own pages in place
    if length > 16:
        before = window.tables[0].copy()
        mgr.ensure(0, length - 1, length)
        assert (window.tables[0] == before).all()
        assert window.allocator.forks == 0


def test_retirement_returns_both_groups_pages():
    mgr = manager()
    full, window = mgr.groups
    free = (full.allocator.free_pages, window.allocator.free_pages)
    for slot, length in ((0, 40), (1, 12)):
        gate = mgr.gate(np.arange(4), 4, length)
        mgr.map_slot(slot, gate[1], gate[2])
        mgr.ensure(slot, 0, length)
    assert full.allocator.used_pages == 10 + 3
    assert window.allocator.used_pages == 4 + 3
    mgr.free_slot(0)
    mgr.free_slot(1)
    assert (full.allocator.free_pages, window.allocator.free_pages) == free
    assert full.allocator.available() == free[0]        # reservations too
    assert window.allocator.available() == free[1]
    assert not full.tables.any() and not window.tables.any()
    stats = mgr.stats()
    assert set(stats["groups"]) == {"full", "window"}
    assert stats["groups"]["window"]["peak_used_pages"] == 7


def test_two_groups_of_one_kind_are_told_apart_by_name():
    """Two window sizes in one graph: statistics and gauges are keyed by
    the group's name, which carries the capacity where kinds repeat."""
    mgr = GroupedKVManager(SLOTS, [
        CacheGroup("full", 64, (0,)),
        CacheGroup("window", 16, (1,), name="window16"),
        CacheGroup("window", 8, (2,), name="window8")], PAGE)
    gate = mgr.gate(np.arange(4), 4, 36)
    mgr.map_slot(0, gate[1], gate[2])
    mgr.ensure(0, 0, 40)
    groups = mgr.stats()["groups"]
    assert {n: g["used_pages"] for n, g in groups.items()} == {
        "full": 10, "window16": 4, "window8": 2}
    with pytest.raises(MXNetError, match="window16.*window8"):
        mgr.gate_pages(3)


def test_admission_is_all_groups_or_none():
    # the full group's pool holds two whole slots and no more
    mgr = manager(pool_pages=2 * 16 + 1)
    full, window = mgr.groups
    for slot in (0, 1):
        gate = mgr.gate(np.arange(4), 4, 64)
        assert gate is not None
        mgr.map_slot(slot, gate[1], gate[2])
    assert mgr.gate(np.arange(4), 4, 64) is None        # backpressure
    # the refused request left nothing reserved in the window group
    assert window.allocator.available() == window.allocator.free_pages - 2 * 4
    mgr.free_slot(0)
    assert mgr.gate(np.arange(4), 4, 64) is not None


@pytest.mark.parametrize("what", ["gate_pages", "restore_slot"])
def test_restoring_pages_is_refused_by_name(what):
    mgr = manager()
    args = (3,) if what == "gate_pages" else (0, np.ones(16, bool), 3)
    with pytest.raises(MXNetError, match="'window' group"):
        getattr(mgr, what)(*args)
