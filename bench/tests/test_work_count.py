"""Two seeds give every cell the same work: the same shape table, the
same bucket split and the same σ-step counts (only data values differ)."""

import pytest

import work_count


@pytest.mark.parametrize("workload", ["direct_pgn", "direct_tall",
                                      "cv10_pgn", "served_pgn"])
def test_two_seeds_same_work(tiny, workload):
    r = tiny(workload)
    a, b = (work_count.count(r["cfg"], r["traffic"], s)
            for s in (5, 3000000017))
    assert a["shapes"] == b["shapes"]
    assert a.get("buckets") == b.get("buckets")
    assert a["steps"] == b["steps"]
    L = r["traffic"].get("path_length", r["cfg"]["path_length"])
    assert all(s[-1] == L for s in a["steps"])


def test_full_size_served_table_is_half_and_half():
    import harness
    from conftest import resolve

    r = resolve("served_pgn")
    split = work_count.bucket_split(r["traffic"])
    assert split == {"256x16384": 8, "256x32768": 8}
    shapes = harness.request_table(r["traffic"])
    assert len(set(shapes)) == 16
    assert min(n for n, _ in shapes) == 150 and max(n for n, _ in shapes) == 256
