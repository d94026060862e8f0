"""Serving cells: open-loop requests through ``ServingEngine``.

Set-up makes the weights from the seed, builds the engine at the cell's
slots and ``max_seq``, and warms every shape the window will use: one
prefill per length of the cell's prompt grid, the decode step, and the
slot scatter on every slot. With ``fill_slots`` in the mix, the slots are
then filled so that the window opens in steady state.

The window submits each request when it is due (the host clock, from the
window's opening) and steps the engine while any request is queued or
active. Token times are read on the host: a request's first token when
``engine._admit`` returns (its prefill has synced), every later one when
``engine.step()`` returns. After the close the engine keeps stepping,
with no new arrivals, until every request due in the window holds its
first token (at most ``DRAIN_S``), so that a late first token counts its
wait.

The check runs the plain reference over a sample of finished requests,
drawn from the seed with the longest among them, and reads the widest
gap by which a served (greedy) token's logit lies below the reference's
best logit at its position. Under the fault ``control`` the int8
reference takes the program's place: at the same positions it reads the
gap of the token that the int8 reference ranks first.
"""
from __future__ import annotations

import time

import numpy as np

import traffic_gen
from harness import BenchError, flat_shapes, log

DRAIN_S = 60.0
FAULTS = ("token", "control")     # see run.execute


def _program():
    import repro.models.layers as layers
    from repro.configs.base import ArchConfig
    from repro.models import transformer as tf
    from repro.serving.engine import Request, ServingEngine
    return ArchConfig, tf, layers, Request, ServingEngine


def build_params(run, ref):
    """The cell's weights, after checking that the program's parameter
    tree has the layout the reference assumes."""
    ArchConfig, tf, layers, _, _ = _program()
    arch = run.config["arch"]
    cfg = ArchConfig(**arch)
    prog = flat_shapes(layers.abstract_params(tf.model_template(cfg)))
    bad = ref.check_layout(arch, prog)
    if bad:
        raise BenchError(bad)
    return cfg, ref.make_params(arch, run.jax_seed())


def setup(run, ref):
    import jax
    _, _, _, Request, ServingEngine = _program()
    cell, mix = run.cell, run.traffic
    cfg, params = build_params(run, ref)
    jax.block_until_ready(params)
    engine = ServingEngine(cfg, params, slots=cell["slots"],
                           max_seq=cell["max_seq"], greedy=True,
                           degrade=None, max_queue=1 << 20)
    _instrument(run, engine)
    grid = cell["prompt_grid"]
    rng = run.seed_rng("warm")
    n_warm = max(len(grid), cell["slots"])
    with run.span("warmup"):
        for i in range(n_warm):
            prompt = rng.integers(0, cfg.vocab_size, grid[i % len(grid)],
                                  dtype=np.int32)
            engine.submit(Request(uid=-1 - i, prompt=prompt,
                                  max_new_tokens=2))
        engine.run_to_completion()
    if engine.events:
        raise BenchError(f"warm-up tripped invariants: {engine.events}")
    tracker = Tracker()
    if mix.get("fill_slots"):
        arrs = traffic_gen.fill(mix, cell["slots"], cfg.vocab_size, grid,
                                run.seed_rng("fill"), cell["max_seq"])
        with run.span("fill"):
            for a in arrs:
                req = Request(uid=a.uid, prompt=a.prompt,
                              max_new_tokens=a.max_new_tokens)
                engine.submit(req)
                tracker.add(a, due=None, req=req)
            engine.step()
        now = time.perf_counter()
        tracker.update(engine, now, now)
    run.data.update(engine=engine, cfg=cfg, params=params, Request=Request,
                    tracker=tracker)
    if run.data.get("fault") == "token":
        _break_tokens(engine, cfg.vocab_size)


def _instrument(run, engine):
    """Host spans around the engine's admission (prefill + pool scatter)
    and decode step; the admission's end is when first tokens are held."""
    admit, decode = engine._admit, engine._decode_step
    marks = run.data.setdefault("marks", {})

    def timed_admit(finished):
        with run.span("admit"):
            admit(finished)
        marks["admit_end"] = time.perf_counter()

    def timed_decode(finished):
        with run.span("decode"):
            decode(finished)

    engine._admit, engine._decode_step = timed_admit, timed_decode


def _break_tokens(engine, vocab):
    """Fault for the harness's own test: every decoded token is replaced
    where the decode step produces it."""
    pick = engine._decode_for

    def broken(mode):
        fn = pick(mode)

        def step(*args):
            tok, finite, cache = fn(*args)
            return (tok + 1) % vocab, finite, cache
        return step
    engine._decode_for = broken


class Tracker:
    """Per-request host times: due, start of the admitting step, and
    when each token was held."""

    def __init__(self):
        self.reqs = {}        # uid -> dict
        self.inflight = {}    # uid -> Request

    def add(self, arrival, due, req=None):
        self.reqs[arrival.uid] = dict(due=due, plen=len(arrival.prompt),
                                      times=[], admit_step=None, req=req)
        if req is not None:
            self.inflight[arrival.uid] = req

    def attach(self, uid, req):
        self.reqs[uid]["req"] = req
        self.inflight[uid] = req

    def update(self, engine, step_start, step_end, admit_end=None):
        """Attribute the tokens each in-flight request gained in the step
        just taken. Returns how many requests got their first token."""
        firsts = 0
        for uid, req in list(self.inflight.items()):
            rec = self.reqs[uid]
            have = len(rec["times"])
            new = len(req.out_tokens) - have
            if new > 0:
                if have == 0:
                    rec["times"].append(admit_end if admit_end else step_end)
                    rec["admit_step"] = step_start
                    new -= 1
                    firsts += 1
                rec["times"].extend([step_end] * new)
            if req.state.terminal():
                del self.inflight[uid]
        return firsts


def window(run):
    engine = run.data["engine"]
    Request = run.data["Request"]
    tracker = run.data["tracker"]
    cell, mix, cfg = run.cell, run.traffic, run.data["cfg"]
    arr = traffic_gen.arrivals(mix, run.seconds, cfg.vocab_size,
                               cell["prompt_grid"], run.seed_rng("arrivals"),
                               cell["max_seq"])
    marks = run.data["marks"]
    steps = []
    lateness = []
    t0 = time.perf_counter()
    run.window_open = t0
    close = t0 + run.seconds
    trace_at = t0 + cell.get("trace_from", 0.4) * run.seconds
    trace_to = trace_at + cell.get("trace_seconds", 3.0)
    i = 0
    while True:
        now = time.perf_counter()
        if now >= close:
            break
        run.trace_window(now >= trace_at, now >= trace_to)
        while i < len(arr) and t0 + arr[i].due_s <= now:
            a = arr[i]
            req = Request(uid=a.uid, prompt=a.prompt,
                          max_new_tokens=a.max_new_tokens)
            tracker.add(a, due=t0 + a.due_s)
            lateness.append(now - (t0 + a.due_s))
            if engine.submit(req) is None:
                tracker.attach(a.uid, req)
            i += 1
        if engine.active or engine.queue:
            _step(run, engine, tracker, steps, marks)
        else:
            nxt = t0 + arr[i].due_s if i < len(arr) else close
            time.sleep(max(min(nxt, close) - time.perf_counter(), 0.0))
    run.trace_window(True, True)
    t_close = time.perf_counter()
    # drain: every request due in the window gets its first token
    deadline = t_close + DRAIN_S
    while time.perf_counter() < deadline and any(
            not r["times"] and r["req"] is not None
            and not r["req"].state.terminal()
            for r in tracker.reqs.values() if r["due"] is not None):
        _step(run, engine, tracker, None, marks)
    run.data.update(t0=t0, t_close=t_close, t_drained=time.perf_counter(),
                    steps=steps, lateness=lateness, arrivals=len(arr))
    due = [r for r in tracker.reqs.values() if r["due"] is not None]
    run.attempted = len(due)
    run.failed = sum(1 for r in due if not r["times"])
    log(f"serve: {len(arr)} requests due in {run.seconds:.0f} s, "
        f"{sum(1 for r in due if r['times'])} served a first token, "
        f"{len(steps)} steps in the window, generator late by at most "
        f"{max(lateness, default=0.0) * 1e3:.1f} ms")
    for ts, te, firsts, lens in sorted(steps, key=lambda s: s[0] - s[1])[:3]:
        inner = ", ".join(f"{n} {(b - a) * 1e3:.1f} ms" for n, a, b in
                          run.spans if n in ("admit", "decode")
                          and ts <= a and b <= te)
        log(f"serve: long step {(te - ts) * 1e3:.1f} ms at "
            f"{ts - t0:.2f} s ({inner}; {firsts} admitted, {len(lens)} "
            f"active before)")


def _step(run, engine, tracker, steps, marks):
    lens = [len(r.prompt) + len(r.out_tokens) + 1
            for r in engine.active.values()]
    marks["admit_end"] = None
    ts = time.perf_counter()
    with run.span("step"):
        engine.step()
    te = time.perf_counter()
    firsts = tracker.update(engine, ts, te, marks["admit_end"])
    if steps is not None:
        steps.append((ts, te, firsts, lens))


# ---------------------------------------------------------------------------
# Check
# ---------------------------------------------------------------------------


def sample(run):
    """Finished requests to check: the longest, then others drawn from the
    seed until ``check_tokens`` served tokens are in the sample."""
    from repro.serving.engine import State
    done = [r for r in run.data["tracker"].reqs.values()
            if r["req"] is not None and r["req"].state is State.DONE]
    if not done:
        return []
    done.sort(key=lambda r: (-(r["plen"] + len(r["req"].out_tokens)),
                             r["req"].uid))
    out, rest = [done[0]], done[1:]
    order = run.seed_rng("sample").permutation(len(rest))
    want = run.cell["check_tokens"]
    for j in order:
        if sum(len(r["req"].out_tokens) for r in out) >= want:
            break
        out.append(rest[j])
    return out


def gap_fn(arch, ref, max_seq, n_out, control):
    """Jitted: (params, tokens (1,max_seq), start, served (n_out,),
    n_valid) -> (widest gap of the served tokens below the reference's
    best, and with ``control`` the widest gap of the int8 reference's
    first choice)."""
    import jax
    import jax.numpy as jnp

    def fn(params, tokens, start, served, n_valid):
        idx = jnp.minimum(start + jnp.arange(n_out), max_seq - 1)
        valid = jnp.arange(n_out) < n_valid
        h = ref.hidden(arch, params, tokens)[0]
        lg = ref.logits(arch, params, h[idx])
        best = lg.max(-1)
        got = jnp.take_along_axis(lg, served[:, None], -1)[:, 0]
        gap = jnp.where(valid, best - got, 0.0).max()
        if not control:
            return gap, jnp.float32(0.0)
        hq = ref.hidden(arch, params, tokens, quant=True)[0]
        pick = ref.logits(arch, params, hq[idx], quant=True).argmax(-1)
        cgot = jnp.take_along_axis(lg, pick[:, None], -1)[:, 0]
        return gap, jnp.where(valid, best - cgot, 0.0).max()

    return jax.jit(fn)


def finish(run, ref):
    """Free the engine, run the reference over the sample, set the checks."""
    import jax.numpy as jnp
    engine = run.data.pop("engine")
    events = list(engine.events)
    del engine
    run.data["tracker"].inflight.clear()
    cell = run.cell
    control = run.data.get("fault") == "control"
    max_seq, n_out = cell["max_seq"], cell["check_max_out"]
    picked = sample(run)
    fn = gap_fn(run.config["arch"], ref, max_seq, n_out, control)
    widest, widest_ctl, n_tok = 0.0, 0.0, 0
    t = time.perf_counter()
    for r in picked:
        req = r["req"]
        toks = np.zeros((1, max_seq), np.int32)
        seq = list(req.prompt) + list(req.out_tokens[:-1])
        toks[0, :len(seq)] = seq
        served = np.zeros((n_out,), np.int32)
        k = min(len(req.out_tokens), n_out)
        served[:k] = req.out_tokens[:k]
        g, gc = fn(run.data["params"], jnp.asarray(toks),
                   jnp.int32(len(req.prompt) - 1), jnp.asarray(served),
                   jnp.int32(k))
        widest = max(widest, float(g))
        widest_ctl = max(widest_ctl, float(gc))
        n_tok += k
    log(f"check: {len(picked)} requests, {n_tok} served tokens against the "
        f"fp32 reference ({time.perf_counter() - t:.1f} s)")
    if control:
        log(f"check: the program read {widest!r}; the int8 control takes "
            f"its place")
        widest = widest_ctl
    run.data["checked_tokens"] = n_tok
    run.check("max_logit_gap", widest if n_tok else float("inf"),
              cell["limits"]["max_logit_gap"])
    run.check("invariant_events", len(events), 0)
