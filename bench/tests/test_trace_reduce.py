"""trace_reduce on a small .xplane.pb: busy time as a union of overlapping
ops, kernel time by name, idle gaps and their host labels."""
import pytest

import trace_reduce as tr

MS = 1_000_000_000        # picoseconds in a millisecond

# device ops (ms): haar [0, 2), fusion [1, 3) overlapping it, haar [5, 6);
# a second device plane: one op [0, 4).  Host: a serve frame over [0, 10)
# and an engine frame covering the idle gap [3, 5).
XSPACE = f"""
planes {{
  id: 1  name: "/device:TPU:0"
  lines {{ id: 1  name: "XLA Modules"  timestamp_ns: 0
    events {{ metadata_id: 3  offset_ps: 0  duration_ps: {9 * MS} }} }}
  lines {{ id: 2  name: "XLA Ops"  timestamp_ns: 0
    events {{ metadata_id: 1  offset_ps: 0  duration_ps: {2 * MS} }}
    events {{ metadata_id: 2  offset_ps: {1 * MS}  duration_ps: {2 * MS} }}
    events {{ metadata_id: 1  offset_ps: {5 * MS}  duration_ps: {1 * MS} }} }}
  event_metadata {{ key: 1 value {{ id: 1  name: "haar_pallas.1" }} }}
  event_metadata {{ key: 2 value {{ id: 2  name: "fusion.7" }} }}
  event_metadata {{ key: 3 value {{ id: 3  name: "jit_serve" }} }}
}}
planes {{
  id: 2  name: "/device:TPU:1"
  lines {{ id: 1  name: "XLA Ops"  timestamp_ns: 0
    events {{ metadata_id: 1  offset_ps: 0  duration_ps: {4 * MS} }} }}
  event_metadata {{ key: 1 value {{ id: 1  name: "fusion.9" }} }}
}}
planes {{
  id: 3  name: "/host:CPU"
  lines {{ id: 1  name: "python"  timestamp_ns: 0
    events {{ metadata_id: 1  offset_ps: 0  duration_ps: {10 * MS} }}
    events {{ metadata_id: 2  offset_ps: {2500 * MS // 1000}
              duration_ps: {3 * MS} }}
    events {{ metadata_id: 3  offset_ps: {2 * MS}  duration_ps: {5 * MS} }} }}
  event_metadata {{ key: 1 value {{ id: 1  name: "$server.py:300 submit" }} }}
  event_metadata {{ key: 2 value {{ id: 2  name: "$engines.py:277 _col_matmul" }} }}
  event_metadata {{ key: 3 value {{ id: 3  name: "$threading.py:355 wait" }} }}
}}
"""


@pytest.fixture(scope="module")
def xplane(tmp_path_factory):
    from jax.profiler import ProfileData
    path = tmp_path_factory.mktemp("trace") / "plugins" / "profile" / "t"
    path.mkdir(parents=True)
    f = path / "host.xplane.pb"
    f.write_bytes(ProfileData.text_proto_to_serialized_xspace(XSPACE))
    return str(f)


def test_find_xplane(xplane):
    import os
    root = os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.dirname(xplane))))
    assert tr.find_xplane(root) == xplane


def test_load_reads_ops_line_only(xplane):
    devices, host = tr.load(xplane)
    assert sorted(devices) == ["/device:TPU:0", "/device:TPU:1"]
    assert [op.name for op in devices["/device:TPU:0"]] == [
        "haar_pallas.1", "fusion.7", "haar_pallas.1"]
    assert len(host) == 3


def test_union_of_overlapping_ops():
    assert tr.union([(0, 2), (1, 3), (5, 6), (6, 6)]) == [(0, 3), (5, 6)]
    ops = [tr.Op("a", 0, 2), tr.Op("b", 1, 2), tr.Op("a", 5, 1)]
    assert tr.busy_ns(ops) == 4


def test_reduce_busy_kernels_and_gaps(xplane):
    r = tr.reduce(xplane, window_ns=8e6)
    # plane 0 busy [0, 3) + [5, 6) = 4 ms; plane 1 [0, 4) = 4 ms; mean 4 ms
    assert r.busy_ns == pytest.approx(4e6)
    assert r.devices == 2
    by = tr.time_by_name(tr.matching(r.ops, "haar"))
    assert by == {"haar_pallas.1": (2, 3e6)}
    assert r.top_ops[0] == ("fusion.9", pytest.approx(4e-3))
    # plane 0's gaps: [3, 5) and [6, 8); the first lies inside the engine
    # frame, the second inside the serve frame only (the wait is skipped)
    assert r.idle_gaps == [("$engines.py:277 _col_matmul",
                            pytest.approx(2e-3)),
                           ("$server.py:300 submit", pytest.approx(2e-3))]


def test_ops_cut_by_the_window_are_not_whole_calls(xplane):
    r = tr.reduce(xplane, window_ns=5.5e6)
    assert [op.name for op in tr.matching(r.ops, "haar")] == ["haar_pallas.1"]
    assert r.busy_ns == pytest.approx((3e6 + 0.5e6 + 4e6) / 2)


def test_no_device_plane_is_an_error(tmp_path):
    from jax.profiler import ProfileData
    f = tmp_path / "x.xplane.pb"
    f.write_bytes(ProfileData.text_proto_to_serialized_xspace(
        'planes { id: 1 name: "/host:CPU" }'))
    with pytest.raises(ValueError):
        tr.reduce(str(f), 1e6)
