"""Replicated read path: differential + failover suite (ISSUE 9
flagship).

The contract (DESIGN.md §15): followers replaying the leader's shipped
checkpoints + log suffixes converge BYTE-IDENTICALLY to the leader —
live view, per-record versions, applied watermark, counting matrix —
and a failover promotion at an arbitrary (randomized) schedule position
yields a leader whose final state byte-matches the uninterrupted-leader
oracle. Read-your-writes tokens must never route a read to a replica
that has not applied the token's write (directed + property tests), and
replica lag exports through ``ReplicatedQueryService.freshness`` /
``merge_freshness`` / ``Monitor``.
"""
import os
import threading
import zlib

import numpy as np
import pytest
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import events as ev
from repro.core import snapshot as snap
from repro.core.event_ingest import EventIngestor, IngestConfig
from repro.core.eventlog import EventLog
from repro.core.index import AggregateIndex
from repro.core.query import merge_freshness
from repro.core.replication import ReplicatedQueryService, ReplicationGroup
from repro.core.sharded_index import ShardedPrimaryIndex
from test_differential import assert_byte_identical, gen_workload

PCFG = snap.PipelineConfig(n_users=8, n_groups=4, n_dirs=16)

PUMP_EVERY = 2      # leader pumps every 2 produced batches
CKPT_EVERY = 4      # leader checkpoints (= ships) every 4 batches
SYNC_EVERY = 3      # followers sync every 3 batches


def _workload(seed, n_ops=350, take=48):
    stream = ev.EventStream(start_fid=1)
    gen_workload(stream, n_ops, seed)
    names = {0: "fs", **stream.names}
    batches = []
    while len(stream):
        batches.append(stream.take(take))
    return batches, names


def _factory(mode, n_shards):
    def make():
        primary = ShardedPrimaryIndex(n_shards)
        ing = EventIngestor(
            IngestConfig(mode=mode, pad_to=64, max_buffer_events=100,
                         freshness_window=1e9, update_aggregates=True),
            PCFG, primary, AggregateIndex())
        return primary, ing
    return make


def _group(mode, n_shards, ckpt_dir):
    return ReplicationGroup(
        EventLog(), _factory(mode, n_shards),
        n_partitions=max(n_shards, 2), batch_size=48,
        ckpt_dir=str(ckpt_dir))


def _steps(n_batches):
    steps = []
    for bi in range(n_batches):
        steps.append(("produce", bi))
        if (bi + 1) % PUMP_EVERY == 0:
            steps.append(("pump", None))
        if (bi + 1) % CKPT_EVERY == 0:
            steps.append(("ckpt", None))
        if (bi + 1) % SYNC_EVERY == 0:
            steps.append(("sync", None))
    return steps


def _run(group, steps, batches, names, failover_at=None):
    """Drive the schedule; at step index ``failover_at`` the leader
    "dies" (its volatile state is simply abandoned — the log and the
    shipped checkpoint are the durable surface) and the freshest
    follower is promoted mid-schedule."""
    failed_over = False
    for si, (op, arg) in enumerate(steps):
        if failover_at is not None and si == failover_at \
                and group.followers and not failed_over:
            group.failover()
            failed_over = True
        if op == "produce":
            group.produce(batches[arg], names=names if arg == 0 else None)
        elif op == "pump":
            group.pump()
        elif op == "ckpt":
            group.checkpoint()
        else:
            group.sync_followers()
    return failed_over


_ORACLES = {}


def _oracle(ckpt_root, mode, n_shards, seed=11):
    """The uninterrupted leader: same schedule, no followers, drained
    at log end — the byte-identity reference."""
    key = (mode, n_shards, seed)
    if key not in _ORACLES:
        batches, names = _workload(seed)
        g = _group(mode, n_shards,
                   os.path.join(str(ckpt_root), f"oracle-{mode}-{n_shards}"))
        _run(g, _steps(len(batches)), batches, names)
        g.leader.pipeline.drain()
        _ORACLES[key] = g.leader
    return _ORACLES[key]


def _assert_replica_equals(rep, oracle, ctx):
    assert_byte_identical(rep.primary.live(), oracle.primary.live(), ctx)
    for path in oracle.primary.live()["path"]:
        assert rep.primary.lookup(str(path)) == \
            oracle.primary.lookup(str(path)), (ctx, path)
    assert rep.applied_seq() == oracle.applied_seq(), ctx
    np.testing.assert_array_equal(rep.ingestor.counts,
                                  oracle.ingestor.counts, err_msg=ctx)
    assert rep.ingestor.counts_exact and oracle.ingestor.counts_exact, ctx


@pytest.fixture(scope="module")
def oracle_dir(tmp_path_factory):
    return tmp_path_factory.mktemp("repl-oracles")


# ---------------------------------------------------------------------------
# follower convergence (the differential matrix)
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["eager", "buffered"])
@pytest.mark.parametrize("n_shards", [1, 4])
def test_followers_converge_byte_identical(mode, n_shards, oracle_dir,
                                           tmp_path):
    """Two followers — one attached from genesis, one bootstrapped
    MID-RUN from a shipped checkpoint (after the log truncated history
    behind it) — both converge byte-identically to the leader AND to
    the uninterrupted oracle."""
    batches, names = _workload(seed=11)
    group = _group(mode, n_shards, tmp_path / "ship")
    group.add_follower()                      # genesis follower
    steps = _steps(len(batches))
    mid = len(steps) // 2
    for si, (op, arg) in enumerate(steps):
        if si == mid:
            # mid-run bootstrap: a checkpoint must exist by now, and
            # history behind it may already be truncated
            assert group._ckpt_path is not None
            group.add_follower()
        if op == "produce":
            group.produce(batches[arg], names=names if arg == 0 else None)
        elif op == "pump":
            group.pump()
        elif op == "ckpt":
            group.checkpoint()
        else:
            group.sync_followers()
    group.leader.pipeline.drain()
    group.sync_followers(drain=True)          # shutdown barrier: log end
    oracle = _oracle(oracle_dir, mode, n_shards)
    ctx = f"mode={mode} shards={n_shards}"
    _assert_replica_equals(group.leader, oracle, ctx + " leader")
    assert len(group.followers) == 2
    for rid, rep in group.followers.items():
        _assert_replica_equals(rep, oracle, f"{ctx} follower={rid}")


def test_truncation_happened_under_followers(tmp_path):
    """The convergence above must not be vacuous: with followers
    syncing (and advancing their holds), leader checkpoints really do
    retire log history."""
    batches, names = _workload(seed=11)
    group = _group("eager", 1, tmp_path / "ship")
    group.add_follower()
    _run(group, _steps(len(batches)), batches, names)
    assert sum(p.base for t in group.log.topics.values()
               for p in t.partitions) > 0


# ---------------------------------------------------------------------------
# failover: promoted follower byte-matches the uninterrupted oracle
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("mode", ["eager", "buffered"])
@pytest.mark.parametrize("n_shards", [1, 4])
@pytest.mark.parametrize("kill_seed", [0, 1, 2])
def test_failover_matches_oracle(mode, n_shards, kill_seed, oracle_dir,
                                 tmp_path):
    """Kill the leader at a RANDOMIZED schedule position, promote, run
    the rest of the schedule through the promoted leader, drain: the
    final state must byte-match the uninterrupted-leader oracle."""
    batches, names = _workload(seed=11)
    steps = _steps(len(batches))
    rng = np.random.default_rng(
        zlib.crc32(repr((mode, n_shards, kill_seed)).encode()))
    # kill somewhere after the first sync so a follower exists & has
    # state; the promotion itself replays whatever the follower lacks
    kill_at = int(rng.integers(4, len(steps)))
    group = _group(mode, n_shards, tmp_path / "ship")
    group.add_follower()
    group.add_follower()
    failed_over = _run(group, steps, batches, names, failover_at=kill_at)
    assert failed_over
    group.leader.pipeline.drain()
    oracle = _oracle(oracle_dir, mode, n_shards)
    ctx = f"mode={mode} shards={n_shards} kill_at={kill_at}"
    _assert_replica_equals(group.leader, oracle, ctx)
    # promotion rebound produce routing to exactly the ingestor's table
    assert group.leader.pipeline._prod_names == \
        dict(group.leader.ingestor._name), ctx
    # the dead leader's consumer group no longer pins retention
    assert ("metadata-events", "index-pipeline") not in group.log.holds
    assert not any(k[1] == "index-pipeline" for k in group.log.offsets)


def test_failover_without_followers_raises(tmp_path):
    group = _group("eager", 1, tmp_path / "ship")
    with pytest.raises(ValueError, match="no follower"):
        group.failover()


# ---------------------------------------------------------------------------
# read-your-writes token routing
# ---------------------------------------------------------------------------

def test_ryw_token_never_served_stale(tmp_path):
    """Directed: a token-bearing read must be served at an applied
    watermark >= the token — by a fresh follower when one exists, by
    the (caught-up) leader otherwise."""
    batches, names = _workload(seed=19)
    group = _group("eager", 1, tmp_path / "ship")
    group.add_follower()
    svc = ReplicatedQueryService(group)
    token = group.produce(batches[0], names=names)
    # nobody applied yet: the leader must catch itself up to serve
    out = svc.query("find_by_glob", "/fs/*", token=token)
    assert out["freshness"]["replica"] == 0
    assert out["freshness"]["token"] >= token
    assert svc.stats["leader_catchups"] == 1
    # follower synced: the token read routes to it, not the leader
    group.sync_followers(drain=True)
    out = svc.query("find_by_glob", "/fs/*", token=token)
    assert out["freshness"]["replica"] != 0
    assert out["freshness"]["token"] >= token
    # a token from the future of everything produced is loud
    with pytest.raises(ValueError, match="ahead of everything produced"):
        svc.query("find_by_glob", "/fs/*", token=group.token + 10_000)


@settings(max_examples=10, deadline=None)
@given(st.integers(0, 1 << 30))
def test_ryw_token_property(seed):
    """Random interleavings of produce / leader pump / follower sync /
    token reads: every token-bearing response was served at an applied
    watermark >= its token, whichever replica answered."""
    import tempfile
    rng = np.random.default_rng(seed)
    batches, names = _workload(seed=int(rng.integers(1 << 16)), n_ops=120,
                               take=32)
    group = ReplicationGroup(
        EventLog(), _factory("eager", 1), n_partitions=2, batch_size=32,
        ckpt_dir=tempfile.mkdtemp())
    group.add_follower()
    group.add_follower()
    svc = ReplicatedQueryService(group)
    token = 0
    bi = 0
    for _ in range(30):
        r = rng.random()
        if r < 0.35 and bi < len(batches):
            token = group.produce(batches[bi],
                                  names=names if bi == 0 else None)
            bi += 1
        elif r < 0.55:
            group.pump()
        elif r < 0.75:
            for rep in list(group.followers.values()):
                if rng.random() < 0.7:
                    group._sync_replica(rep)
        else:
            out = svc.query("find_by_glob", "/fs/*", token=token)
            served = out["freshness"]["token"]
            assert served >= token, (seed, token, served,
                                     out["freshness"]["replica"])
    group.close()


def test_tokenless_reads_spread_by_cache_affinity(tmp_path):
    """Distinct query keys partition across follower caches (affinity
    routing); a REPEATED key pins to one follower, so its cache serves
    every repeat."""
    batches, names = _workload(seed=7, n_ops=120)
    group = _group("eager", 1, tmp_path / "ship")
    group.add_follower()
    group.add_follower()
    for i, b in enumerate(batches):
        group.produce(b, names=names if i == 0 else None)
    group.leader.pipeline.drain()
    group.sync_followers(drain=True)
    svc = ReplicatedQueryService(group)
    served = {svc.query("find_by_glob",
                        f"/fs/f{i}*")["freshness"]["replica"]
              for i in range(12)}
    assert served == set(group.followers)      # both followers serve
    assert svc.stats["leader_reads"] == 0
    # one key, many reads: one home replica, cache hits after the first
    homes = [svc.query("find_by_glob", "/fs/*")["freshness"]
             for _ in range(4)]
    assert len({h["replica"] for h in homes}) == 1
    assert all(h["cached"] for h in homes[1:])


# ---------------------------------------------------------------------------
# scatter-gather
# ---------------------------------------------------------------------------

def test_query_many_matches_leader_answers(tmp_path):
    """Scatter-gather over replicas returns, per request, exactly what
    the leader alone would return — order preserved."""
    batches, names = _workload(seed=13)
    group = _group("eager", 4, tmp_path / "ship")
    group.add_follower()
    group.add_follower()
    for i, b in enumerate(batches):
        group.produce(b, names=names if i == 0 else None)
    group.leader.pipeline.drain()
    group.sync_followers(drain=True)
    svc = ReplicatedQueryService(group)
    requests = [("find_by_glob", "/fs/*"), ("world_writable",),
                ("per_user_usage",), ("top_storage_users", 3),
                ("find_by_glob", "/fs/f*"), ("most_small_files", 2)]
    got = svc.query_many(requests, token=group.token)
    want = group.leader.service.query_batch(requests)
    assert len(got) == len(want)
    replicas_used = set()
    for g, w in zip(got, want):
        replicas_used.add(g["freshness"]["replica"])
        a, b = g["result"], w["result"]
        if isinstance(b, np.ndarray):
            assert np.array_equal(a, b)
        else:
            assert a == b
    assert len(replicas_used) > 1              # it actually scattered


# ---------------------------------------------------------------------------
# lag export + teardown
# ---------------------------------------------------------------------------

def test_replica_lag_exported_and_merged(tmp_path):
    batches, names = _workload(seed=17, n_ops=120)
    group = _group("eager", 1, tmp_path / "ship")
    group.add_follower()
    svc = ReplicatedQueryService(group)
    for i, b in enumerate(batches):
        group.produce(b, names=names if i == 0 else None)
    group.leader.pipeline.drain()              # leader fresh, follower cold
    fr = svc.freshness()
    assert fr["replicas"] == 1
    assert fr["replica_lag"] == group.leader.applied_seq() > 0
    assert fr["replica_seqs"][0] == group.leader.applied_seq()
    # merge_freshness: the deployment trails by its WORST replica
    merged = merge_freshness([fr, dict(fr, replica_lag=0)])
    assert merged["replica_lag"] == fr["replica_lag"]
    # Monitor exports the marks
    from repro.core.monitor import Monitor, MonitorConfig
    mon = Monitor(MonitorConfig(max_fids=1 << 10), query_service=svc)
    out = mon.run(ev.EventStream(), warmup=False)
    assert out["replicas"] == 1
    assert out["replica_lag"] == fr["replica_lag"]
    # ... and goes to zero once the follower syncs
    group.sync_followers(drain=True)
    assert svc.freshness()["replica_lag"] == 0


def test_remove_follower_releases_retention(tmp_path):
    """A dead (never-syncing) follower pins the log at genesis via its
    bootstrap hold; decommissioning it must let checkpoints truncate."""
    batches, names = _workload(seed=29, n_ops=120)
    group = _group("eager", 1, tmp_path / "ship")
    rep = group.add_follower()                 # attaches hold at genesis
    rid, grp_name = rep.rid, rep.group
    for i, b in enumerate(batches):
        group.produce(b, names=names if i == 0 else None)
    group.checkpoint()                         # wants to truncate...
    bases = [p.base for t in group.log.topics.values()
             for p in t.partitions]
    assert sum(bases) == 0                     # ...pinned by the follower
    group.remove_follower(rid)
    assert ("metadata-events", grp_name) not in group.log.holds
    group.log.truncate("metadata-events")
    bases = [p.base for t in group.log.topics.values()
             for p in t.partitions]
    assert sum(bases) > 0                      # retention proceeds


# ---------------------------------------------------------------------------
# reads, churn and follower sync at once
# ---------------------------------------------------------------------------

def _bootstrapped_group(tmp_path, n_followers, seed=29):
    """A 2-shard group whose leader applied and shipped the first half
    of a workload before ``n_followers`` followers bootstrapped from the
    shipped checkpoint. Returns (group, the unproduced second half)."""
    batches, names = _workload(seed=seed, n_ops=300)
    group = _group("eager", 2, tmp_path / "ship")
    half = len(batches) // 2
    for i, b in enumerate(batches[:half]):
        group.produce(b, names=names if i == 0 else None)
    group.leader.pipeline.drain()
    group.checkpoint()
    for _ in range(n_followers):
        group.add_follower()
    return group, batches[half:]


def test_tokenless_reads_under_churn_never_reach_leader(tmp_path):
    """Reader threads send token-less queries while the leader takes
    churn (produce, pump, ship every 4 batches) and the followers sync:
    every read is served by a follower, and once the log is drained the
    followers byte-match the leader."""
    group, rest = _bootstrapped_group(tmp_path, 2)
    svc = ReplicatedQueryService(group)
    mix = [("find_by_glob", f"/fs/*{i}*") for i in range(8)] + \
        [("world_writable",), ("per_user_usage",)]
    done = threading.Event()
    errors = []

    def reader():
        try:
            extra = 1
            while extra:
                if done.is_set():
                    extra -= 1
                for name, *args in mix:
                    svc.query(name, *args)
        except BaseException as e:             # pragma: no cover
            errors.append(repr(e))

    def churner():
        for k, b in enumerate(rest):
            group.produce(b)
            group.pump()
            if (k + 1) % 4 == 0:
                group.checkpoint()
        done.set()

    def syncer():
        while not done.wait(0.01):
            group.sync_followers()

    threads = [threading.Thread(target=f)
               for f in (churner, syncer, reader, reader, reader)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=600)
    assert not errors, errors
    assert svc.stats["leader_reads"] == 0
    assert svc.stats["follower_reads"] == svc.stats["queries"] > 0
    group.leader.pipeline.drain()
    group.sync_followers(drain=True)
    for rep in group.followers.values():
        _assert_replica_equals(rep, group.leader, f"follower {rep.rid}")


def test_followers_byte_match_promoted_leader_after_failover(tmp_path):
    """Kill the leader with an unsynced tail in the log: the promoted
    follower applies everything produced, and the surviving followers,
    synced to the new leader's shipped checkpoint, byte-match it."""
    group, rest = _bootstrapped_group(tmp_path, 3)
    group.sync_followers()
    for b in rest:                             # the tail no follower saw
        group.produce(b)
    promoted = group.failover(drain=True)
    assert promoted.applied_seq() == group.token
    group.checkpoint()
    group.sync_followers(drain=True)
    assert len(group.followers) == 2
    for rep in group.followers.values():
        _assert_replica_equals(rep, promoted, f"follower {rep.rid}")
