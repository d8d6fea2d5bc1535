"""Deterministic chaos harness for among-device failover tests.

A :class:`Chaos` wraps a :class:`~repro.runtime.Runtime` and executes a
*scripted fault schedule*: faults are keyed to scheduler ticks, fire
immediately BEFORE the tick they are scheduled at executes, and mutate only
simulation state (device liveness flags, broker registrations, channel
wiring) — no threads, no wall-clock, no randomness, so every run of a chaos
scenario is bit-for-bit reproducible and can be compared against its
fault-free twin.

Fault vocabulary:

* ``kill_server(tick, device, ssrc, crash=True)`` — the serving device dies.
  ``crash=True`` is an announced death (``broker.mark_down`` fires the
  ``down`` event at once); ``crash=False`` is a *silent* death — the device
  merely stops heartbeating and serving, and the broker only learns of it
  when the registration's lease expires (``Runtime(lease_ticks=...)``).
* ``kill_server_mid_batch(tick, device, ssrc, after_n=1)`` — arms a tripwire
  on the server's request channel: the device dies the instant its
  ``after_n``-th request of that tick lands, i.e. mid-gather with earlier
  requests already stranded on the dead endpoint.  This is the scenario the
  in-flight failover exists for.
* ``kill_server_mid_flush(tick, device, ssrc, ssink, after_answers=N)`` —
  arms a tripwire on the serving sink's answer paths (eager ``apply`` and
  fused ``push_wire``): the device dies the instant the ``after_answers``-th
  answer of that tick lands, i.e. MID-FLUSH — requests the batcher already
  popped off the request channel are in its hands, invisible to the down
  event's channel purge, and must reach the orphan ledger instead of being
  served by the corpse.
* ``revive_server(tick, device, ssrc)`` — the device returns and re-registers
  under its original registration (same reg_id, so a preferred server wins
  its bindings back).
* ``kill_device(tick, device)`` / ``revive_device(tick, device)`` — generic
  liveness flips (publishers, clients); announced via ``mark_down`` on every
  registration the device holds.
* ``sever(tick, pub_channel, rx)`` / ``restore(tick, pub_channel, rx)`` —
  cut/mend one subscriber's data-plane link: frames published while severed
  never reach that consumer (the broker is oblivious — control and data
  planes fail independently).
* ``at(tick, fn, label)`` — escape hatch for bespoke faults.

All mutations funnel through ``_kill``/``_revive`` so tests and examples
(``examples/failover_offloading.py``) exercise exactly the code paths the
runtime's failover fabric watches — one copy of the fault semantics.
"""
from __future__ import annotations

from typing import Callable, Dict, Iterable, List, Optional, Sequence, Tuple


def lcg_stream(seed: int = 0):
    """Deterministic uniform(0,1) stream (32-bit LCG) — the chaos harness's
    stand-in for randomness: same seed, same traffic, every run."""
    state = (int(seed) & 0xFFFFFFFF) or 1
    while True:
        state = (1664525 * state + 1013904223) & 0xFFFFFFFF
        yield state / 2.0 ** 32


def zipf_tenants(n: int, tenants: Sequence[str], s: float = 1.1,
                 seed: int = 0) -> List[str]:
    """Assign ``n`` clients to tenant ids with a Zipf(s) popularity skew —
    tenant k's mass ∝ 1/(k+1)^s, so the first tenant dominates the way a
    real fleet's biggest customer does.  Deterministic in ``seed``."""
    weights = [1.0 / (k + 1) ** s for k in range(len(tenants))]
    total = sum(weights)
    cumulative, acc = [], 0.0
    for w in weights:
        acc += w / total
        cumulative.append(acc)
    rng = lcg_stream(seed)
    out: List[str] = []
    for _ in range(int(n)):
        u = next(rng)
        for tid, edge in zip(tenants, cumulative):
            if u <= edge:
                out.append(tid)
                break
        else:
            out.append(tenants[-1])
    return out


def burst_schedule(n_ticks: int, base: int = 1, burst: int = 0,
                   burst_at: Iterable[int] = (), width: int = 1
                   ) -> List[int]:
    """Arrivals-per-tick script: ``base`` sustained load with scripted
    overload windows of ``burst`` arrivals starting at each tick in
    ``burst_at`` (0-based, ``width`` ticks wide).  Ticks are script indices
    — the caller maps them onto runtime ticks."""
    sched = [int(base)] * int(n_ticks)
    for t0 in burst_at:
        for t in range(int(t0), min(int(n_ticks), int(t0) + int(width))):
            sched[t] = int(burst)
    return sched


def tenant_arrivals(n_ticks: int, tenants: Sequence[str],
                    schedule: Sequence[int], s: float = 1.1,
                    seed: int = 0) -> List[List[str]]:
    """Per-tick tenant-tagged request script: tick t injects
    ``schedule[t]`` requests, each drawn from the Zipf tenant skew.  The
    flattened draw order is identical to ``zipf_tenants(sum(schedule))``,
    so per-tenant totals are reproducible whatever the tick shaping."""
    flat = zipf_tenants(sum(int(c) for c in schedule[:n_ticks]),
                        tenants, s=s, seed=seed)
    out, i = [], 0
    for t in range(int(n_ticks)):
        c = int(schedule[t]) if t < len(schedule) else 0
        out.append(flat[i:i + c])
        i += c
    return out


def lossy_endpoint(fabric, ep, req_policy, ans_policy=None, name=""):
    """Make a query endpoint's links lossy (DESIGN.md §10): install a fault
    link on its request channel and — because the endpoint mints per-client
    answer channels lazily on the first routed answer — shadow
    ``ep.client_channel`` so every answer channel, including ones born
    after a teardown/activation purge, gets its own link.  Answer links
    derive per-client seeds from ``ans_policy.seed``, so the fault schedule
    is deterministic per (endpoint, client) regardless of arrival order.
    Returns the list of installed links (grows as clients appear)."""
    import dataclasses
    links = [fabric.install(ep.requests, req_policy,
                            name=f"{name}/req" if name else "req")]
    if ans_policy is not None:
        orig = ep.client_channel

        def client_channel(cid):
            ch = orig(cid)
            if id(ch) not in fabric.links:
                pol = dataclasses.replace(
                    ans_policy,
                    seed=(ans_policy.seed + 7919 * int(cid)) & 0xFFFFFFFF)
                links.append(fabric.install(
                    ch, pol, name=f"{name}/ans{cid}" if name
                    else f"ans{cid}"))
            return ch
        ep.client_channel = client_channel
    return links


class Chaos:
    def __init__(self, runtime):
        self.rt = runtime
        self._schedule: Dict[int, List[Tuple[Callable[[], None], str]]] = {}
        #: (tick, label) of every fault that fired, in order
        self.log: List[Tuple[int, str]] = []

    # -- schedule construction -------------------------------------------------
    def at(self, tick: int, fn: Callable[[], None],
           label: Optional[str] = "custom") -> "Chaos":
        """``label=None`` schedules silently (internal plumbing like arming
        a tripwire — the real fault logs itself when it fires)."""
        self._schedule.setdefault(int(tick), []).append((fn, label))
        return self

    def kill_server(self, tick: int, device, ssrc, crash: bool = True
                    ) -> "Chaos":
        return self.at(tick, lambda: self._kill(device, ssrc, crash),
                       f"kill {device.name} ({'crash' if crash else 'silent'})")

    def kill_server_mid_batch(self, tick: int, device, ssrc, after_n: int = 1
                              ) -> "Chaos":
        """The fault is logged when the kill actually FIRES (the
        ``after_n``-th request of that tick lands), not when the tripwire
        is armed; if the tick ends with fewer sends, the tripwire disarms
        and a DISARMED entry is logged instead — a vacuous chaos run can
        never masquerade as a survived fault."""
        def arm():
            chan = ssrc.endpoint.requests
            orig_push = chan.push
            seen = [0]

            def tripwire(buf, nbytes=None):
                ok = orig_push(buf, nbytes)
                seen[0] += 1
                if seen[0] == after_n:
                    chan.push = orig_push  # disarm before the kill purges
                    self._kill(device, ssrc, crash=True)
                    self.log.append(
                        (self.rt.ticks,
                         f"kill {device.name} mid-batch (request {after_n})"))
                return ok

            def disarm():
                if chan.push is tripwire:
                    chan.push = orig_push
                    self.log.append(
                        (self.rt.ticks + 1,
                         f"mid-batch kill of {device.name} DISARMED "
                         f"(fewer than {after_n} sends on tick {tick})"))

            chan.push = tripwire
            self.at(tick + 1, disarm, label=None)
        return self.at(tick, arm, label=None)

    def kill_server_mid_flush(self, tick: int, device, ssrc, ssink,
                              after_answers: int = 1) -> "Chaos":
        """Die while the batcher is SERVING (vs ``kill_server_mid_batch``,
        which dies while clients are still gathering): the kill fires on the
        ``after_answers``-th answer push of that tick, so the flush's
        remaining popped-but-unserved groups race the death.  Same
        arm/fire/DISARM discipline as the mid-batch tripwire — a vacuous
        run logs DISARMED instead of masquerading as a survived fault."""
        def arm():
            orig_apply = ssink.apply
            orig_push_wire = ssink.push_wire
            seen = [0]
            armed = [True]

            def disarm(quiet: bool = False):
                if not armed[0]:
                    return
                armed[0] = False
                ssink.__dict__.pop("apply", None)
                ssink.__dict__.pop("push_wire", None)
                if not quiet:
                    self.log.append(
                        (self.rt.ticks + 1,
                         f"mid-flush kill of {device.name} DISARMED "
                         f"(fewer than {after_answers} answers on "
                         f"tick {tick})"))

            def fire():
                seen[0] += 1
                if seen[0] == after_answers:
                    disarm(quiet=True)  # restore before the kill purges
                    self._kill(device, ssrc, crash=True)
                    self.log.append(
                        (self.rt.ticks,
                         f"kill {device.name} mid-flush "
                         f"(answer {after_answers})"))

            def apply_wrap(params, inputs, ctx=None):
                out = orig_apply(params, inputs, ctx)
                fire()
                return out

            def push_wire_wrap(payload, nbytes, client_id):
                out = orig_push_wire(payload, nbytes, client_id)
                fire()
                return out

            ssink.apply = apply_wrap
            ssink.push_wire = push_wire_wrap
            self.at(tick + 1, disarm, label=None)
        return self.at(tick, arm, label=None)

    def revive_server(self, tick: int, device, ssrc) -> "Chaos":
        return self.at(tick, lambda: self._revive(device, ssrc),
                       f"revive {device.name}")

    def kill_device(self, tick: int, device) -> "Chaos":
        def fn():
            device.alive = False
            for reg in self._device_regs(device):
                self.rt.broker.mark_down(reg)
        return self.at(tick, fn, f"kill {device.name}")

    def revive_device(self, tick: int, device) -> "Chaos":
        def fn():
            device.alive = True
            for reg in self._device_regs(device):
                self.rt.broker.revive(reg)
        return self.at(tick, fn, f"revive {device.name}")

    def sever(self, tick: int, pub_channel, rx) -> "Chaos":
        def fn():
            if rx in pub_channel.consumers:
                pub_channel.consumers.remove(rx)
        return self.at(tick, fn, "sever channel")

    def restore(self, tick: int, pub_channel, rx) -> "Chaos":
        def fn():
            if rx not in pub_channel.consumers:
                pub_channel.consumers.append(rx)
        return self.at(tick, fn, "restore channel")

    def partition_control(self, tick0: int, tick1: int, device) -> "Chaos":
        """Partition a device's CONTROL plane for ticks [tick0, tick1): its
        heartbeats are lost in the network while the device itself keeps
        running — the broker's lease lapses into SUSPICION (not declared
        death; DESIGN.md §10), clients fail over, and when the partition
        heals the resumed beats win the registration back without
        double-serving anything the dedup layer already settled."""
        self.at(tick0, lambda: self.rt._control_blocked.add(device),
                f"control-partition {device.name}")
        self.at(tick1, lambda: self.rt._control_blocked.discard(device),
                f"heal control-partition {device.name}")
        return self

    def expire_lease(self, tick: int, device, reg) -> "Chaos":
        """Force the registration's lease to lapse on the very next broker
        tick — models a stalled (not crashed) device.  The device must also
        stop heartbeating (``alive = False``): the runtime beats on behalf
        of live devices at the top of every tick, which would refresh the
        backdated lease before the expiry check ever saw it.  Requires a
        leased registration (``lease_ticks`` set)."""
        def fn():
            device.alive = False
            reg.last_beat = -10**9
        return self.at(tick, fn, f"expire lease of {reg.topic}")

    # -- fault primitives --------------------------------------------------------
    def _kill(self, device, ssrc, crash: bool):
        device.alive = False
        ssrc.endpoint.alive = False  # stops serving NOW either way
        if crash and ssrc.registration is not None:
            self.rt.broker.mark_down(ssrc.registration)
        # silent death: the broker finds out at lease expiry

    def _revive(self, device, ssrc):
        device.alive = True
        ssrc.endpoint.alive = True
        if ssrc.registration is not None:
            self.rt.broker.revive(ssrc.registration)

    def _device_regs(self, device):
        for run in device.runs:
            for e in run.pipe.elements.values():
                reg = getattr(e, "registration", None)
                if reg is not None:
                    yield reg

    # -- execution ---------------------------------------------------------------
    def run(self, n_ticks: int):
        """Drive the runtime ``n_ticks`` ticks, firing each scheduled fault
        immediately before its tick executes (tick numbers are 1-based and
        continue across successive ``run`` calls, matching
        ``Runtime.ticks``)."""
        for _ in range(n_ticks):
            t = self.rt.ticks + 1
            for fn, label in self._schedule.pop(t, ()):
                fn()
                if label is not None:
                    self.log.append((t, label))
            self.rt.tick()
        return self.rt
