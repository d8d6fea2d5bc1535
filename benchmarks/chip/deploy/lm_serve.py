"""Deployment kind ``lm_serve``: a language model behind ``model_serve`` on a
hub ``Device``, with streaming clients (``client_pipeline``) that send
token prompts through ``tensor_query_client`` and hold each answer whole
at their ``appsink``.  Everything goes through the user entry points:
``Runtime``, ``Device``, ``serve_pipeline``, ``client_pipeline``.

The weights are the benchmark's: made on the device from the seed in the
published layout by the configuration's reference module, and converted
for the served program as a checkpoint loader would.

``correct`` compares served tokens with the reference: for a seeded
sample of finished requests (the longest among them), the reference runs
once over prompt and served tokens in float32, and the widest gap by
which a served token's logit lies below the reference's best logit at its
position is held to the configuration's limit.
"""
from __future__ import annotations

import time
from dataclasses import dataclass
from typing import Dict, List, Optional, Tuple

import numpy as np

from benchmarks.chip import harness, traffic as tf


# ---------------------------------------------------------------------------
# weights: published layout -> the served program's layout
# ---------------------------------------------------------------------------

def rotary_permutation(cfg: Dict):
    """Column order that turns the published rotate-half rotary layout of
    the q and k projections into the served program's interleaved pairs
    (its dims ``2i, 2i+1`` rotate together): the same rotation of the same
    values, so every q.k product is unchanged."""
    d, h = cfg["hidden_size"], cfg["num_attention_heads"]
    hd = d // h
    rot = int(hd * cfg["partial_rotary_factor"])
    half = rot // 2
    head = list(range(hd))
    for i in range(half):
        head[2 * i], head[2 * i + 1] = i, half + i
    return np.concatenate([np.asarray(head) + k * hd for k in range(h)])


def to_program(w: Dict, cfg: Dict) -> Dict:
    """Published-layout weights -> the tree ``models/transformer.py``
    serves (list layout, q/k/v biases, LayerNorm scale and bias)."""
    perm = rotary_permutation(cfg)

    def ln(p):
        return {"scale": p["weight"], "bias": p["bias"]}
    layers = []
    for p in w["layers"]:
        layers.append({
            "norm1": ln(p["input_layernorm"]),
            "attn": {"wq": p["q_proj"]["weight"][:, perm],
                     "bq": p["q_proj"]["bias"][perm],
                     "wk": p["k_proj"]["weight"][:, perm],
                     "bk": p["k_proj"]["bias"][perm],
                     "wv": p["v_proj"]["weight"], "bv": p["v_proj"]["bias"],
                     "wo": p["o_proj"]["weight"]},
            "norm2": ln(p["post_attention_layernorm"]),
            "mlp": {"w_gate": p["gate_proj"]["weight"],
                    "w_up": p["up_proj"]["weight"],
                    "w_down": p["down_proj"]["weight"]},
        })
    return {"embed": {"tok": w["embed_tokens"], "head": w["lm_head"]},
            "layers": layers, "final_norm": ln(w["norm"])}


def weights_key(seed: int):
    import jax
    return jax.random.PRNGKey(int(tf.rng_for(seed, "weights").integers(
        0, 2 ** 31 - 1)))


def check_preset(model_cfg, cfg: Dict):
    """The served preset must have the configuration's sizes."""
    got = {"hidden_size": model_cfg.d_model,
           "intermediate_size": model_cfg.d_ff,
           "num_hidden_layers": model_cfg.n_layers,
           "num_attention_heads": model_cfg.n_heads,
           "num_key_value_heads": model_cfg.n_kv_heads,
           "vocab_size": model_cfg.vocab,
           "partial_rotary_factor": model_cfg.rope_frac,
           "rope_theta": model_cfg.rope_theta}
    bad = {k: (v, cfg[k]) for k, v in got.items() if v != cfg[k]}
    if bad or model_cfg.resolved_head_dim != cfg["hidden_size"] // \
            cfg["num_attention_heads"] or model_cfg.norm != "layernorm" \
            or not model_cfg.mlp_glu or model_cfg.tie_embeddings:
        raise ValueError(f"preset {cfg['preset']!r} differs from the "
                         f"configuration: {bad}")


# ---------------------------------------------------------------------------
# the deployment
# ---------------------------------------------------------------------------

class Deployment:
    def __init__(self, cfg: Dict, params):
        from repro.launch import model_serve as ms
        from repro.runtime import Device, Runtime
        self.cfg = cfg
        self.rt = Runtime()
        hub = Device("hub")
        ps = ms.serve_pipeline(model=cfg["preset"], slots=cfg["slots"],
                               max_seq=cfg["max_seq"])
        elem = ps.elements["lm"]
        check_preset(elem.cfg, cfg)
        elem.init_params = lambda rng: params
        hub.add_pipeline(ps, jit=False)
        # the program's compile cache keeps the element alive past this
        # deployment: let it keep no reference to the weights
        del elem.init_params
        self.rt.add_device(hub)
        self.clients: List["Client"] = []

    @property
    def batcher(self):
        from repro.core.batching import StreamingQueryBatcher
        return next(b for b in self.rt._batchers.values()
                    if isinstance(b, StreamingQueryBatcher))

    def client(self, requests) -> "Client":
        """A new streaming client cycling through ``requests``."""
        c = Client(self, len(self.clients), requests)
        self.clients.append(c)
        return c

    def tokens_generated(self) -> int:
        return self.rt.stats()["query_batching"]["tokens_generated"]

    def slot_contexts(self) -> List[int]:
        """Position of each occupied slot's next input token."""
        return [len(r["prompt"]) + len(r["tokens"]) - 1
                for r in self.batcher._slots.values()]


class Client:
    def __init__(self, dep: Deployment, i: int, requests):
        from repro.core.modelserve import TokenPromptSrc
        from repro.launch import model_serve as ms
        from repro.runtime import Device
        self.dev = Device(f"client{i}")
        reqs = list(requests)
        self.run = self.dev.add_pipeline(ms.client_pipeline(
            prompts=";".join(",".join(map(str, r.prompt)) for r in reqs),
            gens=";".join(str(r.gen) for r in reqs)), jit=False)
        self.src = next(e for e in self.run.pipe.elements.values()
                        if isinstance(e, TokenPromptSrc))
        self.requests = reqs
        self.seen_res = self.seen_err = 0  # outcomes already taken
        self.sent_at = 0.0
        dep.rt.add_device(self.dev)

    def assign(self, req):
        """Point the client's source at one request (open loop)."""
        self.src._prompt_list = [tuple(req.prompt)]
        self.src._gen_list = [req.gen]
        self.requests = [req]

    def take(self) -> List[Tuple[Optional[np.ndarray], bool]]:
        """New (answer, failed) outcomes since the last call."""
        log = self.run.sink_log
        res = log.get("res", [])
        errs = sum(len(v) for k, v in log.items() if k.endswith(".error"))
        out = [(np.asarray(b.tensor), False) for b in res[self.seen_res:]]
        out += [(None, True)] * (errs - self.seen_err)
        self.seen_res, self.seen_err = len(res), errs
        return out


# ---------------------------------------------------------------------------
# driving the traffic
# ---------------------------------------------------------------------------

def warm_up(dep: Deployment, lengths, vocab: int):
    """Every shape the window uses: one prefill per prompt length, a tick
    with joins and a tick without.  Each warm-up client stops as soon as
    it holds its answer, so nothing of it runs on into the window."""
    reqs = [tf.Request(0.0, [1 + (i % (vocab - 1)) for i in range(n)], 3)
            for n in lengths]
    waiting = [dep.client([r]) for r in reqs]
    for _ in range(64):
        dep.rt.tick()
        for c in list(waiting):
            if c.take():
                c.dev.alive = False
                waiting.remove(c)
        if not waiting:
            return
    raise RuntimeError("warm-up requests unanswered")


@dataclass
class Outcome:
    req: tf.Request
    due: float                   # host clock the request was due
    sent: float                  # host clock its client started it
    done: float = 0.0            # host clock the answer reached the client
    answer: Optional[np.ndarray] = None
    failed: bool = False


def fill_slots(dep: Deployment, n: int, max_ticks: int = 8):
    """Run until ``n`` streams hold decode slots: the closed loop's first
    wave is prefilled and joined in set-up, so the window opens on a full
    batch, as a server that has been running would be."""
    for _ in range(max_ticks):
        if len(dep.batcher._slots) >= n:
            return
        dep.rt.tick()
    raise RuntimeError(f"{len(dep.batcher._slots)} of {n} slots filled")


def drive_closed_loop(ctx, dep: Deployment, cycles, drain_s: float
                      ) -> Tuple[List[Outcome], float, float]:
    """The window; at the close the clients stop sending.  Where no
    request finished inside the window, the server goes on until one does
    (for the check only), up to ``drain_s``."""
    clients = [dep.client(cyc) for cyc in cycles]
    count = [0] * len(clients)
    outcomes: List[Outcome] = []
    busy = set(range(len(clients)))
    t_fill = time.perf_counter()
    fill_slots(dep, len(clients))
    ctx.tokens_at_open = dep.tokens_generated()

    def collect(now):
        for i, c in enumerate(clients):
            for ans, failed in c.take():
                req = c.requests[count[i] % len(c.requests)]
                outcomes.append(Outcome(req, c.sent_at, c.sent_at, now, ans,
                                        failed))
                count[i] += 1
                c.sent_at = now
                if not c.dev.alive:
                    busy.discard(i)

    for c in clients:
        c.sent_at = t_fill
    t0 = ctx.open_window()
    t_end = t0 + ctx.seconds
    while time.perf_counter() < t_end:
        ctx.tick_tracer()
        dep.rt.tick()
        collect(time.perf_counter())
    t1 = time.perf_counter()
    ctx.tokens_at_close = dep.tokens_generated()
    for c in clients:
        c.dev.alive = False
    while busy and not any(o.answer is not None for o in outcomes) and \
            time.perf_counter() < t1 + drain_s:
        dep.rt.tick()
        collect(time.perf_counter())
    return outcomes, t0, t1


def drive_open_loop(ctx, dep: Deployment, schedule, drain_s: float
                    ) -> Tuple[List[Outcome], float, float]:
    free: List[Client] = []
    active: Dict[int, Tuple[Client, Outcome]] = {}
    outcomes: List[Outcome] = []

    def activate(req, due):
        c = free.pop() if free else dep.client([req])
        c.assign(req)
        c.take()
        c.dev.alive = True
        oc = Outcome(req, due, time.perf_counter())
        active[id(c)] = (c, oc)
        outcomes.append(oc)

    def collect(now):
        for key in list(active):
            c, oc = active[key]
            got = c.take()
            if got:
                oc.answer, oc.failed = got[0]
                oc.done = now
                c.dev.alive = False
                free.append(c)
                del active[key]

    for _ in range(ctx.mix.get("clients_ready", 0)):
        c = dep.client([schedule[0]])
        c.dev.alive = False
        free.append(c)
    t0 = ctx.open_window()
    t_end = t0 + ctx.seconds
    i = 0
    backlog = None
    ctx.backlog_series = series = []     # (seconds into the window, open)
    while True:
        now = time.perf_counter()
        closed = now >= t_end
        if closed and backlog is None:
            # requests due by the close and not answered yet
            backlog = len(active) + sum(1 for r in schedule[i:]
                                        if t0 + r.due <= now)
        while i < len(schedule) and (closed or t0 + schedule[i].due <= now):
            activate(schedule[i], t0 + schedule[i].due)
            i += 1
        if closed and (not active or now >= t_end + drain_s):
            break
        if not active:
            nxt = t0 + schedule[i].due if i < len(schedule) else t_end
            time.sleep(max(0.0, min(nxt, t_end) - now))
            continue
        ctx.tick_tracer()
        dep.rt.tick()
        collect(time.perf_counter())
        if not closed:
            series.append((now - t0, len(active)))
    t_last = time.perf_counter()
    for c, oc in active.values():
        oc.failed = True
    ctx.backlog_at_close = backlog
    ctx.unanswered = len(active)
    return outcomes, t0, t_last


# ---------------------------------------------------------------------------
# spans (traced runs only)
# ---------------------------------------------------------------------------

def install_spans(spans: harness.Spans, dep: Deployment):
    from repro.core.modelserve import ModelServeElement
    from repro.core.plan import ExecutionPlan
    from repro.runtime import Runtime
    spans.wrap(Runtime, "tick", "tick")
    spans.wrap(ModelServeElement, "host_prefill", "host_prefill",
               attrs_fn=lambda self, params, prompt: {"n": len(prompt)})
    spans.wrap(ModelServeElement, "build_admit", "build_admit",
               attrs_fn=lambda self, admits: {"n": len(admits)})
    orig = ExecutionPlan.compiled_serve_tick

    def compiled_serve_tick(plan, state, donate=None):
        fn = orig(plan, state, donate)

        def serve(params, st, inputs):
            join = not next(iter(inputs.values())).meta.get("empty")
            return spans.record(
                "serve_tick.join" if join else "serve_tick.nojoin", fn,
                (params, st, inputs),
                attrs={"contexts": dep.slot_contexts()}, block=True)
        return serve
    spans.patch(ExecutionPlan, "compiled_serve_tick", compiled_serve_tick)


SPAN_NAMES = ("tick", "host_prefill", "build_admit", "serve_tick.join",
              "serve_tick.nojoin")


# ---------------------------------------------------------------------------
# correctness against the reference
# ---------------------------------------------------------------------------

def sample(outcomes: List[Outcome], seed: int, tokens: int) -> List[Outcome]:
    """Seeded sample of answered requests with the longest in it, until it
    holds ``tokens`` served tokens."""
    done = [o for o in outcomes if o.answer is not None]
    if not done:
        return []
    longest = max(done, key=lambda o: (len(o.req.prompt) + o.req.gen,
                                       o.req.gen))
    rest = [o for o in done if o is not longest]
    order = tf.rng_for(seed, "check").permutation(len(rest))
    out, n = [longest], longest.req.gen
    for k in order:
        if n >= tokens:
            break
        out.append(rest[k])
        n += rest[k].req.gen
    return out


def logit_gaps(ref, weights, cfg: Dict, picked: List[Outcome], rows_pad: int,
               control: bool = False) -> Dict:
    """Widest gap of the served tokens below the reference's best logit;
    with ``control``, also the widest gap of the tokens the fp8 control
    puts first at the same positions."""
    import jax.numpy as jnp
    widest, widest_ctl, n_tok = 0.0, 0.0, 0
    for o in picked:
        p, g = len(o.req.prompt), o.req.gen
        ans = [int(t) for t in o.answer]
        seq = np.zeros((cfg["max_seq"],), np.int32)
        seq[:p + g - 1] = o.req.prompt + ans[:-1]
        rows = np.full((rows_pad,), p + g - 2, np.int32)
        rows[:g] = np.arange(p - 1, p - 1 + g)
        lg = np.asarray(ref.logits_at(weights, cfg, jnp.asarray(seq),
                                      jnp.asarray(rows)))[:g]
        best = lg.max(-1)
        widest = max(widest, float(np.max(best - lg[np.arange(g), ans])))
        n_tok += g
        if control:
            lc = np.asarray(ref.logits_at(weights, cfg, jnp.asarray(seq),
                                          jnp.asarray(rows), fp8=True))[:g]
            top = lc.argmax(-1)
            widest_ctl = max(widest_ctl,
                             float(np.max(best - lg[np.arange(g), top])))
    return {"widest": widest, "widest_control": widest_ctl, "tokens": n_tok}


def wrong_lengths(outcomes: List[Outcome]) -> int:
    return sum(1 for o in outcomes if o.answer is not None
               and len(o.answer) != o.req.gen)


# ---------------------------------------------------------------------------
# one run
# ---------------------------------------------------------------------------

def run(ctx) -> Dict:
    import jax
    cfg, mix = ctx.cfg, ctx.mix
    ref = harness.load_reference(cfg)
    vocab = cfg["vocab_size"]
    init = jax.jit(lambda k: to_program(ref.init_weights(cfg, k), cfg))
    params = init(weights_key(ctx.seed))
    dep = Deployment(cfg, params)
    del params
    warm_up(dep, tf.lengths_used(mix), vocab)
    if ctx.spans is not None:
        install_spans(ctx.spans, dep)
    tok0 = dep.tokens_generated()
    if mix["kind"] == "lm_closed_loop":
        cycles = tf.closed_loop_cycles(mix, ctx.seed, vocab)
        outcomes, t0, t1 = drive_closed_loop(ctx, dep, cycles, mix["drain_s"])
        tokens = ctx.tokens_at_close - ctx.tokens_at_open
    else:
        sched = tf.open_loop_schedule(mix, ctx.seed, ctx.seconds, vocab)
        outcomes, t0, t1 = drive_open_loop(ctx, dep, sched, mix["drain_s"])
        tokens = dep.tokens_generated() - tok0
    ctx.close_window(t1)
    lat = [(o.done - o.due) * 1e3 for o in outcomes if o.answer is not None]
    late = [o.sent - o.due for o in outcomes]
    counters = {"tokens_window": tokens, "ticks": dep.rt.ticks,
                "backlog_at_close": getattr(ctx, "backlog_at_close", None),
                "answered": len(lat),
                "generator_late_s": tf.lateness_summary(late),
                "stats": dep.rt.stats()["query_batching"]}
    metrics = {"lm_tokens_per_s": tokens / (t1 - t0)}
    if len(lat) >= 10:
        metrics["lm_latency_p90_ms"] = tf.percentile(lat, 90)
        counters["latency_p50_ms"] = tf.percentile(lat, 50)
        # a backlog that grows over the window shows as a later half
        # slower than the earlier one
        mid = t0 + ctx.seconds / 2
        for half, keep in (("first", lambda o: o.due < mid),
                           ("second", lambda o: o.due >= mid)):
            v = [(o.done - o.due) * 1e3 for o in outcomes
                 if o.answer is not None and keep(o)]
            counters[f"latency_p90_{half}_half_ms"] = \
                tf.percentile(v, 90) if v else None
    ctx.read_memory()
    picked = sample(outcomes, ctx.seed, mix["check_tokens"])
    bad_len = wrong_lengths(outcomes)
    failed = sum(1 for o in outcomes if o.failed and o.due <= t1)
    del dep, outcomes
    harness.free_program()
    weights = jax.jit(lambda k: ref.init_weights(cfg, k))(weights_key(ctx.seed))
    gaps = logit_gaps(ref, weights, cfg, picked,
                      max(g for g, _ in mix["gen_lengths"]),
                      control=ctx.control)
    limit = cfg["limits"]["logit_gap"]
    checks = [("logit_gap", gaps["widest"], limit),
              ("wrong_lengths", bad_len, 0),
              ("unchecked", int(gaps["tokens"] == 0), 0)]
    if mix["kind"] == "lm_open_loop":
        # every request due in the window is answered within the drain
        checks.append(("unanswered", ctx.unanswered, 0))
    out = {"attempted": len(lat) + failed, "failed": failed,
           "metrics": metrics, "checks": checks, "counters": counters,
           "span_names": SPAN_NAMES}
    if ctx.control:
        out["control_checks"] = harness.control_checks(
            checks, "logit_gap", gaps["widest_control"])
    return out
