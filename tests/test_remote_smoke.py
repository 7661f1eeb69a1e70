"""In-process twin of scripts/remote_smoke.sh (ISSUE 15): the 1-prefill
+ 1-remote-decode fleet over real localhost sockets — worker served by
a ReplicaServer thread instead of a second OS process, so the default
test lane proves the same contract the focused script does:

1. hello negotiates the protocol and ships the scheduler digest;
2. traffic migrates prefill→decode THROUGH the wire (KV handoff blob in
   a requeue frame, ≥1 export — no silent in-place fallback pass);
3. outputs token-identical to a mixed control, streams exactly-once;
4. replica_loads carries the remote transport block;
5. killing the worker (server + scheduler torn down) expires the lease,
   only r1 restarts — against a REPLACEMENT worker, the
   operator-restarted-the-host story — and the journal re-places the
   lost work: zero acknowledged requests lost, outputs identical.
"""

import random
import time

import pytest

from llm_based_apache_spark_optimization_tpu.serve.remote import (
    ReplicaServer,
    SocketTransport,
)
from llm_based_apache_spark_optimization_tpu.serve.resilience import (
    RetryPolicy,
)
from llm_based_apache_spark_optimization_tpu.serve.scheduler import (
    ContinuousBatchingScheduler,
    SchedulerPool,
)
from llm_based_apache_spark_optimization_tpu.serve.supervisor import (
    SupervisedScheduler,
)


@pytest.fixture(scope="module")
def tiny_paged_parts():
    import jax
    import jax.numpy as jnp

    from llm_based_apache_spark_optimization_tpu.models import (
        TINY,
        init_params,
    )

    return TINY, init_params(TINY, jax.random.key(0), dtype=jnp.float32)


def _mk(cfg, params, role):
    return ContinuousBatchingScheduler(
        cfg, params, num_slots=2, decode_chunk=4, prompt_bucket=8,
        stop_ids=(2,), max_seq=96, kv_page_size=8,
        phase_role=role,
    )


def test_remote_decode_fleet_end_to_end(tiny_paged_parts):
    cfg, params = tiny_paged_parts
    reqs = [[1, 5, 9 + i] for i in range(4)]
    with _mk(cfg, params, "mixed") as ctl:
        want = [ctl.submit(ids, max_new_tokens=8, seed=40 + i)
                .result(timeout=300) for i, ids in enumerate(reqs)]

    workers = []  # (server, scheduler) pairs, newest = live worker

    def spawn_worker():
        sched = _mk(cfg, params, "decode")
        sched.start()
        srv = ReplicaServer(sched)
        workers.append((srv, sched))
        return srv.address

    addr = spawn_worker()

    def make_replica(i):
        if i == 1:
            # A targeted restart reconnects to the CURRENT worker — the
            # replacement host after a kill, the same one after a blip.
            return SocketTransport(
                workers[-1][0].address, label="r1",
                retry_policy=RetryPolicy(max_attempts=2,
                                         base_delay_s=0.001,
                                         max_delay_s=0.01),
                rpc_timeout_s=5.0,
            )
        return _mk(cfg, params, "prefill")

    def make_pool():
        return SchedulerPool(
            [make_replica(0), make_replica(1)], factory=make_replica,
            max_restarts=3,
            restart_policy=RetryPolicy(max_attempts=4, base_delay_s=0.001,
                                       max_delay_s=0.05),
            rng=random.Random(0), lease_s=0.05, lease_misses=2,
        )

    sup = SupervisedScheduler(
        make_pool, max_restarts=3,
        restart_policy=RetryPolicy(max_attempts=4, base_delay_s=0.001,
                                   max_delay_s=0.05),
        rng=random.Random(0),
    ).start()
    try:
        # Steps 1-3: migrate through the wire, token-identical,
        # exactly-once streams.
        streams = [[] for _ in reqs]
        futs = [sup.submit(ids, max_new_tokens=8, seed=40 + i,
                           on_token=streams[i].append)
                for i, ids in enumerate(reqs)]
        outs = [f.result(timeout=300) for f in futs]
        assert outs == want
        assert streams == outs
        pool = sup._inner
        exports = sum(
            int(r.get("exports", 0))
            for r in (pool.handoff_stats or {}).get("replicas", [])
        )
        assert exports >= 1, "no handoff crossed the wire"
        assert SocketTransport  # step 1 implicitly: hello succeeded

        # Step 4: the loads feed carries the remote transport block.
        loads = {r["replica"]: r for r in pool.replica_loads()}
        tr = loads["r1"]["transport"]
        assert tr["kind"] == "socket" and tr["rpcs"] >= 1

        # Step 5: kill the worker; the replacement the rebuild will
        # find boots first (the pool's live transport still targets
        # the old address, so nothing serves on it until the lease
        # expires); ONLY r1 restarts, and the next wave comes out
        # identical with zero lost.
        srv0, sched0 = workers[0]
        spawn_worker()
        srv0.close()
        sched0.shutdown()
        futs2 = [sup.submit(ids, max_new_tokens=8, seed=40 + i)
                 for i, ids in enumerate(reqs)]
        outs2 = [f.result(timeout=300) for f in futs2]
        assert outs2 == want
        deadline = time.monotonic() + 20
        h = sup.health()
        while time.monotonic() < deadline:
            reps = {r["replica"]: r for r in h.get("replicas", [])}
            if int(reps.get("r1", {}).get("restarts", 0)) >= 1 \
                    and reps["r1"]["state"] in ("ready", "degraded"):
                break
            time.sleep(0.02)
            h = sup.health()
        reps = {r["replica"]: r for r in h["replicas"]}
        assert int(reps["r1"]["restarts"]) >= 1, \
            "worker death never expired the lease"
        assert int(reps["r0"]["restarts"]) == 0
        assert h["lost"] == 0
        # The healed fleet serves through the replacement worker.
        out3 = sup.submit(reqs[0], max_new_tokens=8, seed=40).result(
            timeout=300)
        assert out3 == want[0]
    finally:
        sup.shutdown()
        for srv, sched in workers:
            srv.close()
            sched.shutdown()


def test_remote_prefill_push_and_sigkill_mid_handoff(tiny_paged_parts):
    """In-process twin of the script's PREFILL-worker leg (ISSUE 17):

    1. a remote PREFILL worker joins a fleet beside a local decode
       replica; the hello wires the push pump;
    2. a clean wave must migrate through PUSHED handoffs (≥1 in
       fleet_stats — the pull path never runs for push-capable
       replicas), token-identical, exactly-once streams;
    3. the worker dies (server + scheduler torn down — the SIGKILL
       equivalent) the moment ≥1 new push of the next wave is in
       flight; the lease expires, ONLY r0 restarts — against a
       replacement worker — and the journal re-prefills the lost work
       with delivered stream prefixes suppressed: zero lost, streams
       exactly-once, outputs identical."""
    cfg, params = tiny_paged_parts
    reqs = [[1, 5, 9 + i] for i in range(4)]
    with _mk(cfg, params, "mixed") as ctl:
        want = [ctl.submit(ids, max_new_tokens=8, seed=60 + i)
                .result(timeout=300) for i, ids in enumerate(reqs)]

    workers = []  # (server, scheduler) pairs, newest = live worker

    def spawn_worker():
        sched = _mk(cfg, params, "prefill")
        sched.start()
        srv = ReplicaServer(sched)
        workers.append((srv, sched))
        return srv.address

    spawn_worker()

    def make_replica(i):
        if i == 0:
            return SocketTransport(
                workers[-1][0].address, label="r0",
                retry_policy=RetryPolicy(max_attempts=2,
                                         base_delay_s=0.001,
                                         max_delay_s=0.01),
                rpc_timeout_s=5.0,
            )
        return _mk(cfg, params, "decode")

    def make_pool():
        return SchedulerPool(
            [make_replica(0), make_replica(1)], factory=make_replica,
            max_restarts=3,
            restart_policy=RetryPolicy(max_attempts=4, base_delay_s=0.001,
                                       max_delay_s=0.05),
            rng=random.Random(0), lease_s=0.05, lease_misses=2,
        )

    sup = SupervisedScheduler(
        make_pool, max_restarts=3,
        restart_policy=RetryPolicy(max_attempts=4, base_delay_s=0.001,
                                   max_delay_s=0.05),
        rng=random.Random(0),
    ).start()
    try:
        pool = sup._inner
        # Step 2: the clean wave rides PUSHED handoffs.
        streams = [[] for _ in reqs]
        futs = [sup.submit(ids, max_new_tokens=8, seed=60 + i,
                           on_token=streams[i].append)
                for i, ids in enumerate(reqs)]
        outs = [f.result(timeout=300) for f in futs]
        assert outs == want
        assert streams == outs
        fl = pool.fleet_stats()
        assert int(fl["pushed"]) >= 1, \
            f"no handoff was pushed through the wire: {fl}"
        assert int(fl["push_bytes"]) > 0

        # Step 3: SIGKILL-equivalent mid-handoff, journal re-prefill on
        # the decode sibling. The replacement worker boots BEFORE the
        # kill (the pool's live transport still targets the old
        # address) so the lease-expiry rebuild reconnects on its first
        # attempt instead of racing scheduler boot against the restart
        # budget.
        pushed_before = int(fl["pushed"])
        srv0, sched0 = workers[0]
        spawn_worker()
        streams2 = [[] for _ in reqs]
        futs2 = [sup.submit(ids, max_new_tokens=8, seed=60 + i,
                            on_token=streams2[i].append)
                 for i, ids in enumerate(reqs)]
        deadline = time.monotonic() + 60
        while (int(pool.fleet_stats()["pushed"]) == pushed_before
               and not all(f.done() for f in futs2)
               and time.monotonic() < deadline):
            time.sleep(0.002)
        srv0.close()
        sched0.shutdown()
        outs2 = [f.result(timeout=300) for f in futs2]
        assert outs2 == want
        # Delivered prefixes suppressed: each stream carries its final
        # token sequence exactly once, no duplicates across the replay.
        assert streams2 == outs2
        deadline = time.monotonic() + 20
        h = sup.health()
        while time.monotonic() < deadline:
            reps = {r["replica"]: r for r in h.get("replicas", [])}
            if int(reps.get("r0", {}).get("restarts", 0)) >= 1 \
                    and reps["r0"]["state"] in ("ready", "degraded"):
                break
            time.sleep(0.02)
            h = sup.health()
        reps = {r["replica"]: r for r in h["replicas"]}
        assert int(reps["r0"]["restarts"]) >= 1, \
            "worker death never expired the lease"
        assert int(reps["r1"]["restarts"]) == 0, \
            "the decode sibling restarted — recovery was not targeted"
        assert h["lost"] == 0
    finally:
        sup.shutdown()
        for srv, sched in workers:
            srv.close()
            sched.shutdown()
