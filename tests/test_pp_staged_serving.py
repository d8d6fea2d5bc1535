"""Pipeline-parallel serving as among-device hops (DESIGN.md §8).

The model's layer stack splits into N ``model_serve_stage`` pipelines —
stage k owns layers [k*R/N, (k+1)*R/N) plus its slice of the slot-stacked
decode-cache plan state — and stage k's per-slot boundary activations
stream to stage k+1 over the SAME pub/sub + query fabric clients use:
broker discovery ranks stages, leases detect stage death, §6 reconfig
covers stage swap.  The acceptance contract pinned here:

* staged decode at N ∈ {2, 4} is BITWISE the single-stage ``model_serve``
  answer AND the per-request sequential decode, at batch 1, 4 and 8,
  including mid-generation joins and leaves;
* the staged hop chain computes the same tokens pp_serve's shard_map step
  does (the intra-process pipeline-parallel reference) — same split, two
  transports (tests/sharding/test_pp_shard_map.py, on forged devices);
* killing a MID-CHAIN stage mid-generation loses zero tokens and replays
  ONLY that stage's cache slice: the coordinator re-runs the dead stage's
  retained boundary activations through a standby's prefill/replay verbs
  (never a whole-generation restart — ``prefills`` stays equal to
  ``streams_started``), and every answer is bitwise the fault-free twin's;
* a §6 hot swap of a downstream stage bumps its epoch fence and recovers
  through the SAME stage-local replay rule, bitwise;
* conservation holds per stage — ``hops_dispatched[k] == hops_completed[k]
  + hops_failed[k]`` — and the §7 token law holds at the coordinator
  (soak).
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.batching import StagedStreamingBatcher
from repro.core.element import element_factory
from repro.launch import model_serve as ms
from repro.runtime import Device, Runtime

pytestmark = pytest.mark.ppstage

MAX_SEQ = 32
MODEL = "stablelm-smoke-4l"


def _staged(rt, n_stages, slots=8, prefix="stage"):
    """One device per stage — the among-device chain.  Every stage inits
    from PRNGKey(0) and slices the SAME full tree, so any standby stage's
    params are bitwise the original's."""
    out = []
    for k, ps in enumerate(ms.staged_serve_pipelines(
            model=MODEL, slots=slots, max_seq=MAX_SEQ, n_stages=n_stages)):
        dev = Device(f"{prefix}{k}")
        out.append((dev, dev.add_pipeline(ps, jit=False), ps))
        rt.add_device(dev)
    return out


def _standby(rt, stage, n_stages, slots=8, name="standby"):
    dev = Device(f"{name}{stage}")
    ps = ms.stage_pipeline(model=MODEL, slots=slots, max_seq=MAX_SEQ,
                           stage=stage, n_stages=n_stages)
    run = dev.add_pipeline(ps, jit=False)
    rt.add_device(dev)
    return dev, run, ps


def _mono(rt, slots=8):
    dev = Device("hub")
    ps = ms.serve_pipeline(model=MODEL, slots=slots, max_seq=MAX_SEQ)
    run = dev.add_pipeline(ps, jit=False)
    rt.add_device(dev)
    return dev, run, ps


def _client(rt, i, prompts, gens):
    dev = Device(f"tv{i}")
    run = dev.add_pipeline(ms.client_pipeline(prompts=prompts, gens=gens),
                           jit=False)
    rt.add_device(dev)
    return run


def _answers(run):
    return [np.asarray(b.tensor).tolist() for b in run.sink_log.get("res", [])]


def _coord(rt) -> StagedStreamingBatcher:
    (b,) = [b for b in rt._batchers.values()
            if isinstance(b, StagedStreamingBatcher)]
    return b


_REF_CACHE = {}


def _ref(params, cfg, prompt, gen):
    key = (id(params), tuple(prompt), gen)
    if key not in _REF_CACHE:
        _REF_CACHE[key] = (params, ms.sequential_decode(params, cfg, prompt,
                                                        gen, MAX_SEQ))
    return _REF_CACHE[key][1]


def _assert_conservation(coord: StagedStreamingBatcher):
    st = coord.stats()
    assert st["tokens_generated"] == st["tokens_delivered"] + \
        st["tokens_dropped"] + st["tokens_in_flight"]
    for k in range(1, coord.n_stages):
        led = coord.stage_ledger(k)
        assert led["dispatched"] == led["completed"] + led["failed"], (k, led)


class TestStagedParity:
    @pytest.mark.parametrize("n_stages", [2, 4])
    @pytest.mark.parametrize("n_clients", [1, 4, 8])
    def test_bitwise_vs_sequential_decode(self, n_stages, n_clients):
        """THE tentpole pin: every answer the N-stage chain delivers is
        bitwise the per-request sequential decode of the FULL model —
        splitting the layer stack across among-device hops changes where
        compute happens, never what it computes."""
        gen_mix = ["4", "3;6", "5", "6;3"]
        rt = Runtime(query_batch=8)
        _staged(rt, n_stages)
        cls = [( _client(rt, i, f"{i+1},{i+2},{i+3}",
                         gen_mix[i % len(gen_mix)]), i)
               for i in range(n_clients)]
        rt2 = Runtime(query_batch=8)
        _, mrun, mps = _mono(rt2)
        rt.run(16)
        params, cfg = mrun.params["lm"], mps.elements["lm"].cfg
        for run, i in cls:
            got = _answers(run)
            assert len(got) >= 2
            gens = [int(g) for g in gen_mix[i % len(gen_mix)].split(";")]
            for j, ans in enumerate(got):
                ref = _ref(params, cfg, [i + 1, i + 2, i + 3],
                           gens[j % len(gens)])
                assert ans == ref, f"client {i} answer {j}: {ans} != {ref}"
        _assert_conservation(_coord(rt))

    def test_staged_answers_match_monolithic_runtime(self):
        """Same clients, same ticks, two fabrics: the 2-stage chain's full
        answer streams are bitwise the single-stage ``model_serve``
        runtime's — transport-level equivalence, not just per-answer."""
        outs = []
        for build in ("staged", "mono"):
            rt = Runtime(query_batch=8)
            if build == "staged":
                _staged(rt, 2)
            else:
                _mono(rt)
            cls = [_client(rt, i, f"{i+1},{i+2}", "5") for i in range(4)]
            rt.run(14)
            outs.append([_answers(c) for c in cls])
        staged, mono = outs
        for i, (a, b) in enumerate(zip(staged, mono)):
            assert len(a) >= 2
            assert a == b, f"client {i}: staged {a} != monolithic {b}"

    def test_mid_generation_join_and_leave_staggered(self):
        """Late joiners enter the live slot table mid-chain: downstream
        stages see them only as admit-mask rows in the next hop — both
        sides stay bitwise sequential."""
        rt = Runtime(query_batch=8)
        _staged(rt, 2)
        rt2 = Runtime(query_batch=8)
        _, mrun, mps = _mono(rt2)
        early = [_client(rt, i, f"{i+1},{i+2}", "8") for i in range(4)]
        rt.run(3)                    # early streams mid-generation
        late = [_client(rt, 4 + i, f"{i+11}", "3") for i in range(4)]
        rt.run(17)
        params, cfg = mrun.params["lm"], mps.elements["lm"].cfg
        for i, run in enumerate(early):
            got = _answers(run)
            assert len(got) >= 2
            for ans in got:
                assert ans == _ref(params, cfg, [i + 1, i + 2], 8)
        for i, run in enumerate(late):
            got = _answers(run)
            assert len(got) >= 3
            for ans in got:
                assert ans == _ref(params, cfg, [i + 11], 3)
        _assert_conservation(_coord(rt))


class TestStageChaos:
    def test_mid_chain_stage_kill_stage_local_replay_bitwise(self, chaos):
        """THE §8 chaos pin: stage 1 of a 2-stage chain dies at tick 5 with
        every stream mid-generation.  The coordinator re-binds to the
        standby, replays ONLY stage 1's cache slice from the retained
        boundary activations (prefill + one replay verb per committed
        step), re-merges the parked caches under the next hop's admit mask
        — and every delivered answer is bitwise the fault-free twin's.  No
        generation restarts: ``prefills`` stays ``streams_started`` and
        zero tokens drop (the §7 kill test drops partials and re-prefills;
        the staged chain keeps them — strictly better)."""
        ticks, kill_at = 24, 5

        rt0 = Runtime(query_batch=8)
        _staged(rt0, 2)
        ref = [_client(rt0, i, f"{i+1},{i+2}", "8") for i in range(3)]
        rt0.run(ticks)

        rt = Runtime(query_batch=8)
        stages = _staged(rt, 2)
        _standby(rt, stage=1, n_stages=2)
        got = [_client(rt, i, f"{i+1},{i+2}", "8") for i in range(3)]
        dev1, _, ps1 = stages[1]
        harness = chaos(rt)
        harness.kill_server(kill_at, dev1, ps1.elements["ssrc"], crash=True)
        harness.run(ticks)

        for r0, r1 in zip(ref, got):
            a, b = _answers(r0), _answers(r1)
            assert len(b) >= 2
            assert a == b          # same ticks, same answers — no delay even
        coord = _coord(rt)
        st = coord.stats()
        assert st["stage_replays"] >= 1
        assert st["stage_replay_steps"] >= 1     # mid-generation steps replayed
        assert st["tokens_dropped"] == 0         # never a whole-gen restart
        assert st["prefills"] == st["streams_started"]
        _assert_conservation(coord)

    def test_stage_death_no_standby_stalls_then_resumes(self, chaos):
        """No standby: the chain stalls (conservation still balances — the
        failed hops are ledgered, streams stay in flight) and resumes
        bitwise when the stage revives — §3 lease semantics per stage."""
        ticks, kill_at, revive_at = 26, 4, 12
        rt0 = Runtime(query_batch=8)
        _staged(rt0, 2)
        ref = [_client(rt0, i, f"{i+1}", "6") for i in range(2)]
        rt0.run(ticks)

        rt = Runtime(query_batch=8)
        stages = _staged(rt, 2)
        got = [_client(rt, i, f"{i+1}", "6") for i in range(2)]
        dev1, _, ps1 = stages[1]
        harness = chaos(rt)
        harness.kill_server(kill_at, dev1, ps1.elements["ssrc"], crash=True)
        harness.revive_server(revive_at, dev1, ps1.elements["ssrc"])
        harness.run(ticks)

        coord = _coord(rt)
        st = coord.stats()
        assert st["hops_failed"] >= 1            # the stall is ledgered
        assert st["tokens_dropped"] == 0
        for r0, r1 in zip(ref, got):
            a, b = _answers(r0), _answers(r1)
            assert len(b) >= 1
            for x, y in zip(a, b):
                assert x == y                    # delayed, never different
        _assert_conservation(coord)


def _composite_ref(stage_params, cfg, prompt, gen):
    """Sequential greedy decode of a COMPOSITE staged model — per-stage
    param trees that need not come from one init (a §6 stage swap installs
    fresh weights in ONE slice while the others keep theirs).  Pure
    stage_prefill/stage_decode chaining, the reference the post-swap
    chain must reproduce bitwise."""
    from repro.models import transformer as T
    n = len(stage_params)
    x = jnp.asarray(prompt, jnp.int32)[None]
    caches = []
    for k, p in enumerate(stage_params):
        x, c = T.stage_prefill(p, cfg, k, n, x, MAX_SEQ)
        caches.append(c)
    tok = jnp.argmax(x[0], axis=-1).astype(jnp.int32)
    out = [int(tok)]
    for _ in range(max(0, gen - 1)):
        x = tok[None]
        for k, p in enumerate(stage_params):
            x, caches[k] = T.stage_decode(p, cfg, k, n, x, caches[k])
        tok = jnp.argmax(x[0], axis=-1).astype(jnp.int32)
        out.append(int(tok))
    return out


class TestStageHotSwap:
    def test_swap_downstream_stage_mid_decode(self):
        """§6 reconfig covers stage swap: hot-swapping stage 1's serve
        element mid-generation bumps the stage's epoch fence
        (``serve_epoch``) and the coordinator distrusts its parked slice,
        stage-local-replaying the retained activations onto the NEW
        element.  The swap installs fresh stage-1 weights (reconfig derives
        new-element params from its own rng), so the §8 contract is: no
        stream drops or restarts (history preserved — ``prefills`` stays
        ``streams_started``), every stream runs to full length, and
        generations started after the commit are BITWISE the sequential
        decode of the COMPOSITE model — old stage-0 slice, new stage-1
        slice — i.e. the chain really serves the swapped weights."""
        ticks, swap_at = 24, 4
        rt = Runtime(query_batch=8)
        stages = _staged(rt, 2)
        cls = [_client(rt, i, f"{i+3},{i+4}", "8") for i in range(3)]
        srun0 = stages[0][1]
        _, srun1, ps1 = stages[1]
        rt.run(swap_at)
        rc = rt.reconfigure(srun1, ps1.reconfig().swap(
            "lm", element_factory("model_serve_stage", model=MODEL,
                                  slots="8", max_seq=str(MAX_SEQ),
                                  stage="1", n_stages="2")),
            warm_ticks=1, rng=jax.random.PRNGKey(7))
        rt.run(ticks - swap_at)
        assert rc.status == "committed"
        assert ps1.elements["ssrc"].endpoint.spec["serve_epoch"] >= 1
        coord = _coord(rt)
        st = coord.stats()
        assert st["stage_replays"] >= 1
        assert st["tokens_dropped"] == 0         # history preserved
        assert st["prefills"] == st["streams_started"]   # no restarts
        cfg = ps1.elements["lm"].cfg
        composite = [srun0.params["lm"], srun1.params["lm"]]
        for i, run in enumerate(cls):
            got = _answers(run)
            assert len(got) >= 2
            assert all(len(a) == 8 for a in got)         # full length, always
            # every answer delivered after the first is a generation that
            # started post-commit: bitwise the composite model's decode
            ref = _composite_ref(composite, cfg, [i + 3, i + 4], 8)
            for ans in got[1:]:
                assert ans == ref, f"client {i}: {ans} != composite {ref}"
        _assert_conservation(coord)


@pytest.mark.soak
def test_staged_soak_per_stage_conservation(chaos):
    """200-tick staged decode soak (DESIGN.md §8): 8 clients with mixed
    generation cycles over a 2-stage chain with a standby, one mid-chain
    stage kill + revival mid-run.  Per-stage hop conservation
    (``dispatched == completed + failed``) and the §7 token law must
    balance to the unit at the end, and every delivered answer stays
    bitwise sequential."""
    TICKS, KILL_AT, REVIVE_AT = 200, 60, 100
    N = 8
    rt = Runtime(query_batch=8)
    stages = _staged(rt, 2, slots=4)
    _standby(rt, stage=1, n_stages=2, slots=4)
    gen_mix = ["4", "3;6", "5;2", "6"]
    cls = [_client(rt, i, f"{i+1},{i+2}", gen_mix[i % 4]) for i in range(N)]
    dev1, _, ps1 = stages[1]
    harness = chaos(rt)
    harness.kill_server(KILL_AT, dev1, ps1.elements["ssrc"], crash=True)
    harness.revive_server(REVIVE_AT, dev1, ps1.elements["ssrc"])
    harness.run(TICKS)

    coord = _coord(rt)
    st = coord.stats()
    assert st["tokens_generated"] == st["tokens_delivered"] + \
        st["tokens_dropped"] + st["tokens_in_flight"]
    assert st["tokens_dropped"] == 0             # stage-local replay only
    assert st["streams_finished"] >= N * 10      # the workload really churned
    assert st["stage_replays"] >= 1              # the kill exercised replay
    for k in range(1, coord.n_stages):
        led = coord.stage_ledger(k)
        assert led["dispatched"] == led["completed"] + led["failed"], (k, led)

    rt2 = Runtime(query_batch=8)
    _, mrun, mps = _mono(rt2)
    params, cfg = mrun.params["lm"], mps.elements["lm"].cfg
    for i, run in enumerate(cls):
        gens = [int(g) for g in gen_mix[i % 4].split(";")]
        for j, ans in enumerate(_answers(run)):
            ref = _ref(params, cfg, [i + 1, i + 2], gens[j % len(gens)])
            assert ans == ref, f"client {i} answer {j}"
