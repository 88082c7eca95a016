"""The trace reduction on a small trace recorded on the CPU: busy union,
idle share, per-scope time, the unscoped row and idle gaps named by the
host span they fall in."""
import time

import jax
import jax.numpy as jnp
import pytest

import conftest  # noqa: F401  (puts the repository on sys.path)
from bench import reduce


def _step(x):
    with jax.named_scope("fleetsim.retire"):
        y = jnp.tanh(x @ x)
    with jax.named_scope("fleetsim.scatter"):
        y = y.at[0].add(1.0)
    return jnp.sin(y) @ y                         # outside any scope


@pytest.fixture(scope="module")
def recorded(tmp_path_factory):
    f = jax.jit(_step)
    x = jnp.ones((256, 256), jnp.float32)
    f(x).block_until_ready()
    d = str(tmp_path_factory.mktemp("trace"))
    jax.profiler.start_trace(d)
    with jax.profiler.TraceAnnotation("bench.window"):
        for _ in range(3):
            with jax.profiler.TraceAnnotation("bench.call"):
                f(x).block_until_ready()
            with jax.profiler.TraceAnnotation("bench.fetch"):
                time.sleep(0.05)                  # the device idles here
    jax.profiler.stop_trace()
    return reduce.reduce_trace(reduce.find_xplane(d))


def test_busy_is_a_union_inside_the_window(recorded):
    assert 0 < recorded["busy_s"] < recorded["window_s"]
    assert recorded["window_s"] >= 0.15           # three 50 ms sleeps
    idle = 1 - recorded["busy_s"] / recorded["window_s"]
    assert 0.5 < idle < 1


def test_scopes_and_the_unscoped_row(recorded):
    # CPU ops carry no name stack, so all of them are the unscoped row
    scopes = recorded["scope_s"]
    assert set(scopes) == {"unscoped"} and scopes["unscoped"] > 0
    rows = dict(recorded["breakdown"]["device_ops"])
    assert set(rows) == set(scopes)
    assert len(recorded["breakdown"]["device_ops"]) <= 10


def test_idle_gaps_are_named_by_host_span(recorded):
    gaps = recorded["breakdown"]["idle_gaps"]
    assert gaps[0][0] == "bench.fetch"            # the sleeps dominate
    assert gaps[0][1] >= 0.14
    assert sum(s for _, s in gaps) == pytest.approx(
        recorded["window_s"] - recorded["busy_s"], rel=1e-6)


def test_op_scope_reads_the_innermost_name_on_the_stack():
    tpu_op = {"tf_op": "jit(simulate)/fleetsim.retire/while/body/"
                       "kernels.event_select/add", "hlo_category": "loop fusion",
              "program_id": 7}
    assert reduce.op_scope(tpu_op) == "kernels.event_select"
    assert reduce.op_scope({"tf_op": "jit(simulate)/fleetsim.scatter/"
                                     "scatter-add"}) == "fleetsim.scatter"
    assert reduce.op_scope({"tf_op": "jit(simulate)/while/body/select_n",
                            "hlo_op": "fusion.3"}) == "unscoped"
    assert reduce.op_scope({}) == "unscoped"


def _pb(*fields):
    """Encode (field number, value) pairs: an int as a varint, bytes or
    str as a length-delimited field."""
    def varint(n):
        out = b""
        while True:
            out += bytes([n & 0x7F | (0x80 if n > 0x7F else 0)])
            n >>= 7
            if not n:
                return out
    out = b""
    for num, v in fields:
        if isinstance(v, int):
            out += varint(num << 3) + varint(v)
        else:
            v = v.encode() if isinstance(v, str) else v
            out += varint(num << 3 | 2) + varint(len(v)) + v
    return out


def test_op_metadata_reads_the_name_stack_from_event_metadata(tmp_path):
    # XPlane: name 2, lines 3, event_metadata 4, stat_metadata 5 (map
    # entries: key 1, value 2); XEventMetadata: name 2, stats 5; XStat:
    # metadata_id 1, str_value 5, ref_value 7; XStatMetadata: id 1, name 2
    stat_md = [_pb((1, k), (2, _pb((1, k), (2, n))))
               for k, n in ((1, "tf_op"), (2, "hlo_category"), (3, "loop"))]
    op = _pb((1, 9), (2, "fusion.3"),
             (5, _pb((1, 1), (5, "jit(f)/while/body/fleetsim.retire/add"))),
             (5, _pb((1, 2), (7, 3))), (5, _pb((1, 2), (4, 17))))
    tpu = _pb((1, 1), (2, "/device:TPU:0"), (3, _pb((2, "XLA Ops"))),
              (4, _pb((1, 9), (2, op))), *[(5, s) for s in stat_md])
    host = _pb((1, 2), (2, "/host:CPU"), (4, _pb((1, 1), (2, _pb((2, "x"))))))
    path = tmp_path / "t.xplane.pb"
    path.write_bytes(_pb((1, host), (1, tpu)))
    got = reduce.op_metadata(str(path))
    assert got == {"/device:TPU:0": {"fusion.3": {
        "tf_op": "jit(f)/while/body/fleetsim.retire/add",
        "hlo_category": "loop"}}}
    assert reduce.op_scope(got["/device:TPU:0"]["fusion.3"]) == \
        "fleetsim.retire"


def test_nested_ops_count_their_self_time():
    # a loop op (0-100) holding two body ops, one of which holds another
    ops = [(0, 100, "unscoped"), (10, 40, "fleetsim.retire"),
           (15, 25, "fleetsim.scatter"), (50, 90, "fleetsim.route"),
           (120, 130, "fleetsim.retire")]
    got = reduce.self_times(ops)
    assert got == {"unscoped": 30, "fleetsim.retire": 30,
                   "fleetsim.scatter": 10, "fleetsim.route": 40}
    assert sum(got.values()) == 110               # the union of the ops
