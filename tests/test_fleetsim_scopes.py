"""Every device op of a fleet call sits in a ``fleetsim.*`` / ``kernels.*``
scope.

The benchmark's trace reduction (``bench/reduce.py``) charges each device
op to the innermost such name in its name stack and puts an op outside
every scope in the row ``unscoped``.  These tests compile ``_simulate`` at
a small fleet, for the CPU and for a described v5e chip, and map every
top-level instruction of the entry computation and of every loop
computation (the scan's chunk loop and the step loop inside it, the
retire and drain loops, loops the compiler builds) through the same
``op_scope``.

Instructions with no name of their own are skipped, and their opcodes
are named in ``UNNAMED``: XLA creates them (carry copies and their async
halves, buffer allocations, splat broadcasts of constants, the bodies of
gathers and scatters it expands into loops).  On the chip they stay
``unscoped``.  An instruction an outer ``jit`` inlines without a name
takes the bare call's name (``jit(sweep)/vmap(jit(_simulate))``), which
is no name of its own either.  The sweep's one op outside ``_simulate``
is ``vmap``'s broadcast of the unbatched ``total`` output to the point
axis, in ``CALLER``.
"""
import re

import jax
import jax.numpy as jnp
import pytest

from bench.reduce import op_scope
from test_tpu_compile import one_chip  # noqa: F401  (fixture)

K, R, CAPACITY, DEPTH, POINTS = 16, 64, 32, 16, 4

# opcodes that run nothing on the device, named or not
NOT_RUN = {"parameter", "constant", "get-tuple-element", "tuple", "bitcast"}
# opcodes seen with no name of their own (CPU and v5e, jax 0.9.0)
UNNAMED = {
    "copy", "copy-start", "copy-done",     # carry and layout copies
    "custom-call",                         # AllocateBuffer (v5e)
    "broadcast", "fusion",                 # splat constants of the set-up
    "compare", "and", "convert",           # index bounds of dynamic slices
    "reduce-window",                       # rewritten sums and cumsums
    "iota", "add", "slice",                # gathers and scatters that the
    "dynamic-update-slice",                # v5e sweep expands into loops
}

# the sweep's op outside `_simulate`: vmap broadcasts `total` to the points
CALLER = {"jit(sweep)/vmap()/broadcast_in_dim"}

_COMP = re.compile(r"^(ENTRY )?%?([\w.\-]+) .*\{$")
_INSTR = re.compile(r"^\s+(?:ROOT )?%?[\w.\-]+ = .*?\s([a-z][\w\-]*)\(")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALL_FRAME = re.compile(r"(?:jit|vmap|pjit)\([^/]*\)")


def _computations(hlo: str):
    comps, cur, entry = {}, None, None
    for line in hlo.splitlines():
        m = _COMP.match(line)
        if m:
            cur = m.group(2)
            comps[cur] = []
            entry = cur if m.group(1) else entry
        elif line.startswith("}"):
            cur = None
        elif cur is not None and _INSTR.match(line):
            comps[cur].append(line)
    return comps, entry


def top_level(hlo: str):
    """``(computation, opcode, op_name or None, line)`` for every
    instruction of the entry computation and of each loop's condition
    and body, following loops inside loops."""
    comps, entry = _computations(hlo)
    todo, seen = [entry], []
    while todo:
        c = todo.pop()
        if c in seen:
            continue
        seen.append(c)
        for line in comps[c]:
            if re.search(r"\swhile\(", line):
                todo += re.findall(r"(?:condition|body)=%?([\w.\-]+)", line)
    out = []
    for c in seen:
        for line in comps[c]:
            m = _OP_NAME.search(line)
            out.append((c, _INSTR.match(line).group(1),
                        m.group(1) if m else None, line))
    return out


def unnamed(op_name) -> bool:
    """No ``op_name``, or one made of call frames only."""
    return not op_name or all(_CALL_FRAME.fullmatch(p)
                              for p in op_name.split("/"))


def in_program(op_name: str) -> bool:
    return any("_simulate)" in p for p in op_name.split("/"))


def _compile(case: str, sharding):
    from repro.fleetsim import RequestArrays, SimParams, TopologyArrays
    from repro.fleetsim import simulate_fn
    from repro.fleetsim.core import _simulate
    from repro.netsim import NetParams

    def s(shape=(), dt=jnp.float32, point=False):
        shape = ((POINTS,) if point else ()) + shape
        return jax.ShapeDtypeStruct(shape, dt, sharding=sharding)

    sweep = case == "sweep"
    per_req = lambda dt=jnp.float32: s((R,), dt, point=sweep)
    reqs = RequestArrays(per_req(), per_req(), per_req(),
                         per_req(jnp.int32), per_req(jnp.int32), per_req())
    topo = TopologyArrays(s((K, K), jnp.bool_), s((K, K - 1), jnp.int32),
                          s((K,), jnp.int32), s((K,)))
    params = SimParams(s((), jnp.int32, point=sweep), s((), point=sweep))
    targets, net = s((R, 2), jnp.int32), NetParams(s((K, K)), s((K, K)))
    policy = "round_robin" if sweep else case
    kw = dict(policy=policy, max_forwards=2, discard_on_exhaust=False,
              capacity=CAPACITY, depth=DEPTH)
    if sweep:      # as the benchmark's sweep cell calls it
        points = jax.vmap(simulate_fn(network=True, **kw),
                          in_axes=(0, None, SimParams(0, 0), None, None))

        def sweep(*args):
            return points(*args)
        lowered = jax.jit(sweep).lower(reqs, topo, params, targets, net)
    else:          # as simulate() calls it
        lowered = _simulate.lower(reqs, topo, params, targets, net,
                                  use_pallas=False, use_network=True, **kw)
    return lowered.compile().as_text()


def loop_tree(hlo: str):
    """Every loop of the entry computation as ``(scope, body_scopes,
    loops)``: the loop's own scope, the scopes of its body's named ops,
    and the loops of its body in the same form."""
    comps, entry = _computations(hlo)

    def loops(c):
        out = []
        for line in comps[c]:
            if not re.search(r"\swhile\(", line):
                continue
            body = re.search(r"body=%?([\w.\-]+)", line).group(1)
            names = [_OP_NAME.search(ln) for ln in comps[body]]
            out.append((op_scope({"op_name": _OP_NAME.search(line).group(1)}),
                        {op_scope({"op_name": m.group(1)}) for m in names
                         if m and not unnamed(m.group(1))},
                        loops(body)))
        return out
    return loops(entry)


@pytest.fixture(scope="module")
def compiled(request):
    cache = {}

    def get(target, case, tree=False):
        if (target, case) not in cache:
            sharding = (request.getfixturevalue("one_chip")
                        if target == "v5e" else None)
            hlo = _compile(case, sharding)
            cache[target, case] = (top_level(hlo), loop_tree(hlo))
        return cache[target, case][int(tree)]
    return get


@pytest.mark.parametrize("target", ["cpu", "v5e"])
@pytest.mark.parametrize("case", ["batched_feasible", "round_robin",
                                  "sweep"])
def test_every_named_op_has_a_scope(compiled, target, case):
    instrs = compiled(target, case)
    unscoped, skipped, caller = [], set(), set()
    for comp, opcode, op_name, line in instrs:
        if opcode in NOT_RUN:
            continue
        if unnamed(op_name):
            skipped.add(opcode)
        elif not in_program(op_name):
            caller.add(op_name)
        elif op_scope({"op_name": op_name}) == "unscoped":
            unscoped.append(f"{comp}: {line.strip()[:160]}")
    assert not unscoped, "\n".join(unscoped)
    assert skipped <= UNNAMED, sorted(skipped - UNNAMED)
    assert caller == (CALLER if case == "sweep" else set())
    scopes = {op_scope({"op_name": n}) for _, _, n, _ in instrs if n}
    scoring = {"fleetsim.windows", "kernels.event_select"}
    if case == "batched_feasible":
        assert scoring <= scopes
    else:
        assert not scoring & scopes


@pytest.mark.parametrize("target", ["cpu", "v5e"])
@pytest.mark.parametrize("case", ["batched_feasible", "round_robin",
                                  "sweep"])
def test_scan_and_drain_loops_are_scoped(compiled, target, case):
    instrs = compiled(target, case)
    entry = instrs[0][0]
    loops = {op_scope({"op_name": n}) for c, opcode, n, _ in instrs
             if c == entry and opcode == "while"}
    assert {"fleetsim.scan", "fleetsim.drain"} <= loops
    assert loops <= {"fleetsim.scan", "fleetsim.drain", "fleetsim.retire"}


@pytest.mark.parametrize("target", ["cpu", "v5e"])
@pytest.mark.parametrize("case", ["batched_feasible", "round_robin",
                                  "sweep"])
def test_scan_chunks_nest_the_step_loop(compiled, target, case):
    """The scan runs its steps in chunks: a ``fleetsim.scan`` loop whose
    body is the chunk's own ``fleetsim.scan`` loop over the step, with the
    retire loop inside that.  The outer body holds the loop test, the
    carry selects and what the compiler hoists out of the step; the
    step's phases sit one loop down."""
    outer = [lp for lp in compiled(target, case, tree=True)
             if lp[0] == "fleetsim.scan"
             and any(c[0] == "fleetsim.scan" for c in lp[2])]
    assert len(outer) == 1
    _, scopes, inner = outer[0]
    step = {"fleetsim.event_pop", "fleetsim.route", "fleetsim.admission",
            "fleetsim.scatter"}
    assert "fleetsim.scan" in scopes and not scopes & step, scopes
    assert [lp[0] for lp in inner] == ["fleetsim.scan"]
    _, step_scopes, step_loops = inner[0]
    assert step <= step_scopes
    assert "fleetsim.retire" in {lp[0] for lp in step_loops}
