"""The scans' walk over a table's stack of lanes: a query's segments as
ONE program a rung and spec.

`query/execution.py` `execute_segment_plans` finds the table's stack
in the server's `segment/loader.py` `LaneStacks` (its immutable
segments of one padded size, their lanes stacked on the device to a
power-of-two count of rows, admitted and built on first use) and
launches the ladders whose next programs share a spec as one program
(`ops/kernels.py` `get_stacked_segment_kernel`: ONE segment's closure
looped over the members). Held here to the walk it replaces: the same
blocks bit for bit, one program a spec, a compile surface that does not
grow with the members a walk runs nor with a table's segments between
powers of two, the stack's bytes in the residency ledger, refused where
they do not fit, and gone with any of its members. SSB's 13 shapes over small
dbgen segments without cubes, built as the benchmark builds them, on
the CPU. Nothing here is a measurement.
"""
import json
import os
import sys

import numpy as np
import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH_DIR = os.path.join(REPO, "benchmarks")
for p in (REPO, BENCH_DIR):
    if p not in sys.path:
        sys.path.insert(0, p)

from pinot_tpu.common.metrics import MetricsRegistry, ServerMeter  # noqa: E402
from pinot_tpu.obs import profiler as obs_profiler          # noqa: E402
from pinot_tpu.obs.residency import LEDGER                  # noqa: E402

SEED, EIGHT = 5, 8
SHAPES = ["q1.1", "q1.2", "q1.3", "q2.1", "q2.2", "q2.3", "q3.1", "q3.2",
          "q3.3", "q3.4", "q4.1", "q4.2", "q4.3"]
METERS = (ServerMeter.WALK_STACKED_SEGMENTS,
          ServerMeter.WALK_STACKED_PROGRAMS, ServerMeter.DEVICE_PROGRAMS,
          ServerMeter.DEVICE_PULLS, "xlaCompiles")


def build_segments(base: str, rows: int, segments: int, config: str,
                   name_offset: int = 0):
    from harness import build, cells, tables
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    conf = dict(cells.load_json(BENCH_DIR, "configs", f"{config}.json"),
                rows=rows, segments=segments)
    return [ImmutableSegmentLoader.load(build.build_segment(
        (conf, SEED, name_offset + i, hi - lo, base)))
        for i, (lo, hi) in enumerate(tables.segment_bounds(rows, segments))]


class Walks:
    """Segments of one configuration, the SSB shapes over their pools,
    the stacks of a server of this test's own, and a registry with the
    server's meters bound."""

    def __init__(self, segments):
        from harness import shapes, tables
        from pinot_tpu.segment.loader import LaneStacks
        self.segments = segments
        self.stacks = LaneStacks()
        pools = tables.load_generator("ssb_dbgen").pools()
        self.shapes = {s.name: s for s in shapes.load_family(
            BENCH_DIR, "ssb", pools)}
        self.metrics = MetricsRegistry("server")
        obs_profiler.bind_server_metrics(self.metrics)

    def meters(self) -> dict:
        return {m: self.metrics.meter(m).count for m in METERS}

    def plans(self, name: str, segments=None, index: int = 0):
        from pinot_tpu.pql.optimizer import BrokerRequestOptimizer
        from pinot_tpu.pql.parser import compile_pql
        from pinot_tpu.query.plan import (InstancePlanMaker,
                                          preprocess_request)
        segments = self.segments if segments is None else segments
        shape = self.shapes[name]
        literals = shape.spec["ssb"] if index == 0 else \
            shape.literals(index)
        request = preprocess_request(segments, BrokerRequestOptimizer(
        ).optimize(compile_pql(shape.pql(literals))))
        maker = InstancePlanMaker()
        return [maker.make_segment_plan(s, request) for s in segments]

    def walk(self, plans, stacked: bool = True):
        """-> (blocks, what the meters grew by): the walk over the
        table's stack, or (`stacked` False: no server's stacks) the walk
        of segment programs it replaces."""
        from pinot_tpu.query import execution
        before = self.meters()
        blocks = execution.execute_segment_plans(
            plans, stacks=self.stacks if stacked else None)
        return blocks, {m: n - before[m] for m, n in self.meters().items()}

    def stack(self, segments=None):
        """The table's stack of the first segment's size."""
        segments = self.segments if segments is None else segments
        return self.stacks.for_segments(segments)[segments[0].padded_docs]


def block_of(blk):
    """Everything a block answers, for equality to the bit."""
    assert not isinstance(blk, Exception), blk
    return (blk.agg_intermediates, blk.group_map, blk.selection_rows,
            blk.stats.num_docs_scanned, blk.stats.num_segments_matched,
            blk.stats.total_docs)


@pytest.fixture(scope="module")
def walks(tmp_path_factory):
    return Walks(build_segments(str(tmp_path_factory.mktemp("stacked8")),
                                80_000, EIGHT, "ssb_flat_nocube"))


@pytest.fixture(scope="module")
def dict_walks(tmp_path_factory):
    return Walks(build_segments(str(tmp_path_factory.mktemp("stacked8d")),
                                72_000, EIGHT, "ssb_flat_nocube_dict"))


@pytest.mark.parametrize("shape", SHAPES)
def test_the_stacked_walk_answers_as_the_segment_walk_to_the_bit(
        walks, shape):
    """Integer sums, float sums, group keys and matched counts: each
    member runs the same closure as its segment's own program."""
    plans = walks.plans(shape)
    alone, plain = walks.walk(plans, stacked=False)
    stacked, grown = walks.walk(plans)
    assert [block_of(b) for b in stacked] == [block_of(b) for b in alone]
    # every launch of the walk was a stacked program, ONE a rung and
    # spec (a table's spec follows its segment's scout: the members of
    # a rung may take several), every segment program of the walk it
    # replaces one of its members
    rungs = plain[ServerMeter.DEVICE_PULLS]
    assert grown[ServerMeter.DEVICE_PULLS] == rungs
    assert rungs <= grown[ServerMeter.WALK_STACKED_PROGRAMS] == \
        grown[ServerMeter.DEVICE_PROGRAMS] < plain[ServerMeter.DEVICE_PROGRAMS]
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == \
        plain[ServerMeter.DEVICE_PROGRAMS] >= EIGHT
    assert plain[ServerMeter.WALK_STACKED_PROGRAMS] == 0
    if shape.startswith("q1"):
        assert grown[ServerMeter.WALK_STACKED_PROGRAMS] == rungs == 1


@pytest.mark.parametrize("shape", ["q1.1", "q2.2", "q3.1", "q4.1"])
def test_integer_sums_of_part_lanes_answer_as_the_segment_walk(
        dict_walks, shape):
    """Every column behind a dictionary: both sums of every shape are
    integer part lanes, and exact either way."""
    plans = dict_walks.plans(shape)
    alone, plain = dict_walks.walk(plans, stacked=False)
    stacked, grown = dict_walks.walk(plans)
    assert [block_of(b) for b in stacked] == [block_of(b) for b in alone]
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == \
        plain[ServerMeter.DEVICE_PROGRAMS] > \
        grown[ServerMeter.WALK_STACKED_PROGRAMS] > 0


def test_a_kmax_re_run_is_a_group_of_the_ladders_that_overflowed(
        walks, monkeypatch):
    """The third member's first table reports overflow (forced here):
    the table's rung launches that ladder's next table alone, as a
    stacked program of one member with four times the slots, and the
    answer is the walk's without the re-run."""
    import jax.numpy as jnp
    from pinot_tpu.ops import kernels
    plans = walks.plans("q2.2")
    want, _grown = walks.walk(plans)
    real, tables = kernels.run_stacked_segment_kernel, []

    def overflow_once(padded, filt, aggs, group_spec, select, cols, shapes,
                      n_stack, members):
        outs = real(padded, filt, aggs, group_spec, select, cols, shapes,
                    n_stack, members)
        if group_spec is not None:
            tables.append((group_spec[4], [m[0] for m in members]))
            if len(tables) == 1:
                outs = dict(outs, **{"group.overflow":
                                     outs["group.overflow"].at[2].set(1)})
        return outs
    monkeypatch.setattr(kernels, "run_stacked_segment_kernel",
                        overflow_once)
    got, grown = walks.walk(plans)
    assert [block_of(b) for b in got] == [block_of(b) for b in want]
    (kmax, rows), *others, (kmax2, rows2) = tables
    # the first table's rung: every member with a table, in one or two
    # programs by spec; then the third member of the first alone
    assert len(rows) + sum(len(r) for _k, r in others) == EIGHT
    assert rows2 == [rows[2]] and kmax2 >= 4 * kmax
    # scout, tables, the re-run: a pull each rung, the re-run's own
    assert grown[ServerMeter.DEVICE_PULLS] == 3
    assert grown[ServerMeter.WALK_STACKED_PROGRAMS] == 1 + len(tables)
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == 2 * EIGHT + 1


def test_a_group_of_one_is_the_stacked_program_with_one_member(walks):
    plans = walks.plans("q3.2")
    walks.walk(plans)                     # the stack of eight exists
    alone, _plain = walks.walk(plans[3:4], stacked=False)
    (blk,), grown = walks.walk(plans[3:4])
    assert block_of(blk) == block_of(alone[0])
    assert grown[ServerMeter.WALK_STACKED_PROGRAMS] == \
        grown[ServerMeter.WALK_STACKED_SEGMENTS] == \
        grown[ServerMeter.DEVICE_PULLS] >= 2


def test_a_segment_run_alone_takes_the_walks_program(walks):
    """A pool task or a batch's member (`plan.execute()`) over a segment
    the walks have stacked runs the walk's program with one member:
    nothing the walks did not compile; a segment no walk has stacked
    runs its own."""
    plans = walks.plans("q2.1", index=5)
    walked, _grown = walks.walk(plans)
    before = walks.meters()
    blk = plans[6].execute(walks.stacks)
    grown = {m: n - before[m] for m, n in walks.meters().items()}
    assert block_of(blk) == block_of(walked[6])
    assert grown["xlaCompiles"] == 0
    assert grown[ServerMeter.WALK_STACKED_PROGRAMS] == \
        grown[ServerMeter.WALK_STACKED_SEGMENTS] == \
        grown[ServerMeter.DEVICE_PULLS] >= 2
    assert walks.stacks.holding(plans[6].segment) is not None


def test_a_rung_whose_ladders_have_two_specs_is_two_programs_one_pull(
        walks):
    plans = walks.plans("q1.1")[:4] + walks.plans("q1.3")[4:]
    alone, _plain = walks.walk(plans, stacked=False)
    stacked, grown = walks.walk(plans)
    assert [block_of(b) for b in stacked] == [block_of(b) for b in alone]
    assert grown[ServerMeter.DEVICE_PULLS] == 1
    assert grown[ServerMeter.WALK_STACKED_PROGRAMS] == \
        grown[ServerMeter.DEVICE_PROGRAMS] == 2
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == EIGHT


def test_fewer_members_then_all_again_compile_nothing_new(
        walks, monkeypatch):
    """Eight segments, five of them (three pruned or refused), eight
    again: one program a spec, whatever a walk runs of the stack."""
    from pinot_tpu.ops import kernels
    real, seen = kernels.get_stacked_segment_kernel, set()

    def spy(*args):
        fn = real(*args)
        seen.add(fn)
        return fn
    monkeypatch.setattr(kernels, "get_stacked_segment_kernel", spy)
    for shape in ("q2.2", "q1.2"):
        plans = walks.plans(shape, index=3)
        walks.walk(plans)
        programs = {fn: fn._cache_size() for fn in seen}
        for subset in (plans[:5], plans[2:7], plans):
            _blocks, grown = walks.walk(subset)
            assert grown[ServerMeter.WALK_STACKED_SEGMENTS] > 0
            assert grown["xlaCompiles"] == 0, (shape, len(subset))
            assert {fn: fn._cache_size() for fn in seen} == programs


def _primitives(jaxpr, counts=None) -> dict:
    """{primitive name: count} over `jaxpr` and every jaxpr inside."""
    counts = {} if counts is None else counts
    for eqn in jaxpr.eqns:
        name = eqn.primitive.name
        counts[name] = counts.get(name, 0) + 1
        for value in eqn.params.values():
            for sub in value if isinstance(value, (list, tuple)) \
                    else (value,):
                inner = getattr(sub, "jaxpr", sub)
                if hasattr(inner, "eqns"):
                    _primitives(inner, counts)
    return counts


@pytest.mark.parametrize("case", ["stacked_group_compacted",
                                  "stacked_scan_agg"])
def test_a_stacked_program_holds_one_copy_of_the_segment_body(case):
    """The registered stacked cases against the segment program of
    the same spec: one `while` more, and every other primitive as
    often (one body, not one a member)."""
    import jax
    from pinot_tpu.analysis.contracts import _materialize_tree, fill_static
    from pinot_tpu.ops import kernels
    cases = {name: (make, static, specs) for name, make, static,
             specs in kernels.extra_contract_cases()}
    make, static, specs = cases[case]
    P = kernels.CONTRACT_SHAPE_BUCKETS[0]
    args = fill_static(static, P)
    padded, filt, aggs, group, select, _s_bucket, shapes, slots = args
    cols, packed, leaves, n_live = _materialize_tree(specs, P)
    stacked = _primitives(jax.make_jaxpr(make(*args))(
        cols, packed, leaves, n_live).jaxpr)
    one_cols = {k: np.zeros(shape, cols[k].dtype) for k, shape in shapes}
    rest = iter(leaves)
    params = tuple(next(rest)[0] if s is None else np.zeros((), s)
                   for s in slots)
    base = _primitives(jax.make_jaxpr(kernels.build_segment_kernel(
        padded, filt, aggs, group, select))(
        one_cols, params, np.int32(P)).jaxpr)
    assert stacked.pop("while") == base.pop("while", 0) + 1
    for name in ("cond", "dot_general", "reduce_sum", "scan"):
        assert stacked.get(name, 0) == base.get(name, 0), name
    assert make(*args) is make(*args)


# -- the stack in the residency ledger ---------------------------------------


def lanestack_bytes() -> int:
    return LEDGER.kind_bytes("lanestack")


def stack_entries(table: str = "lineorder") -> dict:
    return {e["owner"]: e["bytes"] for e in LEDGER.snapshot(100_000)[
        "entries"] if e["kind"] == "lanestack" and e["table"] == table}


@pytest.fixture()
def small(tmp_path):
    """Three segments of a size no other test here builds (a stack of
    their own), and a fourth whose padded size differs."""
    segments = build_segments(str(tmp_path), 60_000, 3, "ssb_flat_nocube")
    odd = build_segments(str(tmp_path / "odd"), 5_000, 1,
                         "ssb_flat_nocube", name_offset=3)
    yield Walks(segments), odd[0], str(tmp_path)
    for seg in segments + odd:
        seg.destroy()


def test_the_stack_is_in_the_ledger_under_owners_of_its_own(small):
    walks, _odd, _base = small
    before = stack_entries()
    plans = walks.plans("q2.1")
    walks.walk(plans)
    mine = {o: n for o, n in stack_entries().items() if o not in before}
    assert mine and all(o.startswith("lanestack:") for o in mine)
    # one entry a (column, kind) the plans read: its members' lanes
    # stacked, byte for byte, three members in a bucket of four rows
    stack = walks.stack()
    assert (stack.size, stack.bucket) == (3, 4)
    lanes = {f"{col}.{kind}" for col, kind in plans[0].needed_cols}
    assert {o.rsplit(":", 1)[1] for o in mine} == lanes
    assert all(o.startswith(stack._owner) for o in mine)
    for owner, nbytes in mine.items():
        col, kind = owner.rsplit(":", 1)[1].split(".")
        assert nbytes == stack.bucket * int(
            walks.segments[0].data_source(col).device_lane(kind).nbytes)


@pytest.mark.parametrize("how", ["demote", "reload", "remove"])
def test_a_member_demoted_reloaded_or_removed_drops_the_stack(small, how):
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    walks, _odd, base = small
    plans = walks.plans("q1.2")
    walks.walk(plans)
    first = walks.stack()
    built = {o for o in stack_entries() if o.startswith(first._owner)}
    assert all(first.row(s) is not None for s in walks.segments) and built
    victim = walks.segments[1]
    if how == "demote":
        victim.release_device_lanes()
    elif how == "reload":
        fresh = ImmutableSegmentLoader.load(os.path.join(
            base, victim.segment_name))
        walks.segments[1] = fresh
        victim.destroy()
    else:
        walks.segments.pop(1)
        victim.destroy()
    assert not built & set(stack_entries())      # its lanes are gone
    assert walks.stacks.holding(walks.segments[0]) is None
    plans = walks.plans("q1.2")
    alone, _plain = walks.walk(plans, stacked=False)
    blocks, grown = walks.walk(plans)
    assert [block_of(b) for b in blocks] == [block_of(b) for b in alone]
    again = walks.stack()
    assert again is not first
    assert all(again.row(s) is not None for s in walks.segments)
    assert (again.row(victim) is None) == (how != "demote")
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == len(walks.segments)
    assert set(stack_entries()) - built                 # built anew


def test_a_member_dropped_without_a_release_keeps_its_row(small):
    """A segment collected without a destroy (no release to say it
    left): the stack keeps its row and its programs, and a lane built
    after gives that row one no walk reads."""
    import gc
    walks, _odd, _base = small
    walks.walk(walks.plans("q1.1"))
    stack = walks.stack()
    gone = walks.segments.pop(0)
    gone_row = stack.row(gone)
    del gone
    gc.collect()
    assert stack.members()[gone_row] is None
    # a shape whose lanes the stack has not built yet
    plans = walks.plans("q3.2")
    alone, _plain = walks.walk(plans, stacked=False)
    blocks, grown = walks.walk(plans)
    assert [block_of(b) for b in blocks] == [block_of(b) for b in alone]
    assert walks.stack() is stack
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] >= len(walks.segments)


def test_other_padded_sizes_and_upsert_lanes_run_their_own_programs(small):
    from pinot_tpu.realtime.upsert import ValidDocIds
    walks, odd, _base = small
    assert odd.padded_docs != walks.segments[0].padded_docs
    upsert = walks.segments[2]
    upsert.valid_doc_ids = ValidDocIds()
    upsert.valid_doc_ids.invalidate_many(range(0, 200, 7))
    segments = walks.segments + [odd]
    plans = walks.plans("q1.1", segments)
    alone, _plain = walks.walk(plans, stacked=False)
    blocks, grown = walks.walk(plans)
    assert [block_of(b) for b in blocks] == [block_of(b) for b in alone]
    # the two plain members are one program; the upsert segment and the
    # odd one a program each
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == 2
    assert grown[ServerMeter.WALK_STACKED_PROGRAMS] == 1
    assert grown[ServerMeter.DEVICE_PROGRAMS] == 3
    assert grown[ServerMeter.DEVICE_PULLS] == 1
    # run alone, neither is in a stack: its own program
    assert walks.stacks.holding(odd) is None and \
        walks.stacks.holding(upsert) is None


@pytest.mark.parametrize("refusal", ["budget", "device_memory",
                                     "allocation"])
def test_a_stack_the_device_cannot_hold_leaves_the_ladders_their_lanes(
        small, refusal, monkeypatch):
    """Bytes the server's budget or the device's free memory does not
    admit, or an allocation the device refuses: no stack is built or
    ledgered, and the walk answers over the segments' own lanes with
    their own programs, as a walk without stacks does."""
    from pinot_tpu.segment import loader
    walks, _odd, _base = small
    asked = []
    if refusal == "budget":
        walks.stacks.fits = lambda nbytes: asked.append(nbytes) or False
    elif refusal == "device_memory":
        monkeypatch.setattr(loader, "device_fits",
                            lambda nbytes: asked.append(nbytes) or False)
    else:
        def refuse():
            def stack(lanes):
                asked.append(len(lanes))
                raise RuntimeError("RESOURCE_EXHAUSTED: injected")
            return stack
        monkeypatch.setattr(loader, "_stacker", refuse)
    before = stack_entries()
    plans = walks.plans("q2.1")
    alone, plain = walks.walk(plans, stacked=False)
    blocks, grown = walks.walk(plans)
    assert [block_of(b) for b in blocks] == [block_of(b) for b in alone]
    assert asked and set(stack_entries()) == set(before)
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == \
        grown[ServerMeter.WALK_STACKED_PROGRAMS] == 0
    assert grown[ServerMeter.DEVICE_PROGRAMS] == \
        plain[ServerMeter.DEVICE_PROGRAMS]
    # the refusal is kept: the next walk asks nothing more of it
    n = len(asked)
    walks.walk(plans)
    assert len(asked) == n


def test_the_budget_admits_a_stack_as_it_stands_and_evicts_nothing():
    from pinot_tpu.server.residency_manager import ResidencyManager
    assert ResidencyManager().admits_derived(1 << 60)
    total = LEDGER.total_bytes()
    tight = ResidencyManager(budget_bytes=total + 1000)
    assert tight.admits_derived(1000) and not tight.admits_derived(1001)


def test_pressure_drops_every_stack_and_the_next_walk_admits_it_anew(small):
    walks, _odd, _base = small
    plans = walks.plans("q1.1")
    walks.walk(plans)
    first = walks.stack()
    assert any(o.startswith(first._owner) for o in stack_entries())
    walks.stacks.evict_all()
    assert first.dropped
    assert not any(o.startswith(first._owner) for o in stack_entries())
    _blocks, grown = walks.walk(plans)
    assert walks.stack() is not first
    assert grown[ServerMeter.WALK_STACKED_SEGMENTS] == len(walks.segments)


def test_a_server_wires_its_stacks_to_its_budget_and_its_pressure():
    from pinot_tpu.server.instance import ServerInstance
    server = ServerInstance("stacks_server", num_workers=1,
                            device_bytes_budget=1 << 40)
    stacks = server.executor.executor.lane_stacks
    assert stacks.fits == server.residency.admits_derived
    assert stacks.evict_all in server.residency._pressure_hooks
    assert ServerInstance("another", num_workers=1).executor.executor \
        .lane_stacks is not stacks


def test_two_servers_of_one_process_keep_their_stacks_apart(small):
    """Two servers hosting copies of one table's segments (a replica
    each, the same names): neither's walks replace the other's members,
    so neither restacks when the other walks."""
    from pinot_tpu.segment.loader import ImmutableSegmentLoader
    walks, _odd, base = small
    replica = Walks([ImmutableSegmentLoader.load(os.path.join(
        base, s.segment_name)) for s in walks.segments])
    try:
        walks.walk(walks.plans("q1.1"))
        mine = walks.stack()
        replica.walk(replica.plans("q1.1"))
        theirs = replica.stack()
        walks.walk(walks.plans("q1.1"))
        assert walks.stack() is mine and replica.stack() is theirs
        assert not mine.dropped and not theirs.dropped
    finally:
        for seg in replica.segments:
            seg.destroy()


def test_a_table_growing_8_9_10_segments_compiles_once_for_its_bucket(
        tmp_path, monkeypatch):
    """Eight segments (a bucket of 8 rows), nine (16), ten (16): the
    ninth makes at most one program more a spec, and the tenth compiles
    nothing for a scan."""
    from pinot_tpu.ops import kernels
    walks = Walks(build_segments(str(tmp_path), 20_000, 10,
                                 "ssb_flat_nocube"))
    real, seen = kernels.get_stacked_segment_kernel, {}

    def spy(*args):
        fn = real(*args)
        spec = args[:5] + args[6:]
        seen.setdefault(spec, set()).add(fn)
        return fn
    monkeypatch.setattr(kernels, "get_stacked_segment_kernel", spy)

    def entries() -> dict:
        return {spec: sum(fn._cache_size() for fn in fns)
                for spec, fns in seen.items()}
    try:
        grown = {}
        for n in (8, 9, 10):
            for shape in ("q1.2", "q2.2"):
                plans = walks.plans(shape, walks.segments[:n], index=2)
                _blocks, grown[n, shape] = walks.walk(plans)
            if n == 8:
                at_eight = entries()
            assert walks.stack(walks.segments[:n]).bucket == \
                (8 if n == 8 else 16)
        assert all(grown[n, s][ServerMeter.WALK_STACKED_SEGMENTS] >= n
                   for n, s in grown)
        # a table's spec follows its segment's data (the scout's
        # counts), so new segments may bring new specs; none has more
        # than one program a bucket
        at_ten = entries()
        assert all(at_ten[spec] <= at_eight.get(spec, 0) + 1
                   for spec in at_ten)
        assert grown[10, "q1.2"]["xlaCompiles"] == 0
    finally:
        for seg in walks.segments:
            seg.destroy()


# -- the meters and the benchmark's reading of them ---------------------------


def test_a_stacked_launch_marks_its_members_and_one_program():
    reg = MetricsRegistry("server")
    obs_profiler.bind_server_metrics(reg)
    assert reg.meter(ServerMeter.WALK_STACKED_SEGMENTS).count == 0
    assert reg.meter(ServerMeter.WALK_STACKED_PROGRAMS).count == 0
    obs_profiler.mark_stacked_launch(8)
    obs_profiler.mark_stacked_launch(1)
    assert reg.meter(ServerMeter.WALK_STACKED_SEGMENTS).count == 9
    assert reg.meter(ServerMeter.WALK_STACKED_PROGRAMS).count == 2


def test_segments_per_program_reads_the_two_meters_or_nothing():
    """The benchmark's `segments_per_program`: a counter ratio of the
    two meters; nothing on a server that has neither (a program before
    the stack), so the line leaves it out."""
    from reducers import counter_ratio
    with open(os.path.join(BENCH_DIR, "layer_metrics",
                           "segments_per_program.json")) as fh:
        spec = json.load(fh)
    with open(os.path.join(REPO, "BENCHMARK.json")) as fh:
        entry, = [m for m in json.load(fh)["per_layer"]
                  if m["name"] == "segments_per_program"]
    assert spec["reducer"] == "counter_ratio" and \
        spec["moves"] == entry["moves"] == "latency_p50_ms"
    assert entry["source"] == "program_counter"
    num, = spec["params"]["num"]
    den, = spec["params"]["den"]
    assert (num, den) == (
        "server.metrics.meter.walkStackedSegments.count",
        "server.metrics.meter.walkStackedPrograms.count")
    parent = {"server.metrics.meter.devicePrograms.count": 400}
    assert counter_ratio.reduce({"counters": {"delta": parent}},
                                spec) is None
    assert counter_ratio.reduce({"counters": {"delta": dict(
        parent, **{num: 0, den: 0})}}, spec) is None
    assert counter_ratio.reduce({"counters": {"delta": dict(
        parent, **{num: 754, den: 100})}}, spec) == pytest.approx(7.54)
