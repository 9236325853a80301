"""The general traffic generator and the drivers of the system under test.

A traffic mix is a JSON file under ``bench/traffic/``; its ``mode`` picks
one of the drivers below and the rest are its parameters (rates, bursts,
tenants, windows).  A configuration is a JSON file under
``bench/configs/``.  Everything here is keyed by those two files, so a
new mix or a new deployment of an existing mode is data alone.

Every driver follows the same order: make the data from the seed, warm
every shape the window will use (set-up), measure for ``--seconds``,
then hand back what the window produced for the reference to judge.
An arrival schedule is part of its mix: every run replays it, and the
seed draws the problems and solver seeds that arrive, so the work of a
window does not change with the seed.
"""

from __future__ import annotations

import contextlib
import gc
import math
import re
import resource
import time

import numpy as np

from bench import data

CLOCK = time.perf_counter


# ------------------------------------------------------------- the run
class Run:
    """What one run carries between the harness, a driver and the
    metric readers: the cell's files, the seed, the host spans the
    benchmark writes around its calls into the program, and the counts
    it reads from the program's results."""

    def __init__(self, cell, cfg, traffic, seed, seconds, tracer, t0):
        self.cell, self.cfg, self.traffic = cell, cfg, traffic
        self.seed, self.seconds = seed, seconds
        self.tracer = tracer          # trace.Tracer, or None
        self.t0 = t0                  # wall clock at process start
        self.spans: list[tuple[str, float, float]] = []
        self.counters: dict[str, float] = {}
        self.setup_s = None
        self._compiles = 0
        self._compiles_at_open = None
        self._window_open = False
        # JAX's own timed events (tracing, lowering, compiling, cache
        # reads) that fall inside the window: name -> [count, seconds]
        self.jax_events: dict[str, list] = {}
        import jax
        jax.monitoring.register_event_duration_secs_listener(self._count)

    def _count(self, event, secs, **_kw):
        if event == "/jax/core/compile/backend_compile_duration":
            self._compiles += 1
        if self._window_open:
            rec = self.jax_events.setdefault(event, [0, 0.0])
            rec[0] += 1
            rec[1] += secs

    @contextlib.contextmanager
    def span(self, name: str):
        """A host span, kept in memory and written into the profiler's
        trace when one is being taken."""
        import jax
        t = CLOCK()
        with jax.profiler.TraceAnnotation(name):
            yield
        self.spans.append((name, t, CLOCK()))

    def open_window(self) -> float:
        """End of set-up: records setup_s, how it splits over the set-up
        spans, and the compile count."""
        self.setup_s = time.time() - self.t0
        parts: dict[str, float] = {}
        for name, a, b in self.spans:
            parts[name] = parts.get(name, 0.0) + (b - a)
        parts["other"] = self.setup_s - sum(parts.values())
        self.counters["setup_parts_s"] = parts
        self._compiles_at_open = self._compiles
        self._window_open = True
        return CLOCK()

    def close_window(self) -> None:
        """Compiles since the window opened, read as the window ends."""
        import jax
        jax.monitoring.unregister_event_duration_listener(self._count)
        self._window_open = False
        self.counters["compiles_in_window"] = (
            self._compiles - self._compiles_at_open)

    def trace_on(self) -> None:
        if self.tracer is not None:
            self.tracer.start()

    def trace_off(self) -> None:
        if self.tracer is not None and self.tracer.running:
            self.tracer.stop()


def solver_seed(seed: int, k: int) -> int:
    """The k-th solver seed of a run (31 bits: the program's PRNG)."""
    ss = np.random.SeedSequence([int(seed) % (1 << 63), 7, k])
    return int(ss.generate_state(1, np.uint32)[0] >> 1)


def nu_of(alpha: float, n1: int, n2: int) -> float:
    """The paper's experiment convention nu = 1 / (alpha min(n1, n2))."""
    return 1.0 / (alpha * min(n1, n2))


def percentile_of(name: str) -> float | None:
    m = re.fullmatch(r"latency_p(\d+)_ms", name)
    return float(m.group(1)) if m else None


def percentile(values, q: float) -> float:
    """The q-th percentile by the nearest rank (no interpolation)."""
    v = np.sort(np.asarray(values, np.float64))
    k = max(int(math.ceil(q / 100.0 * len(v))) - 1, 0)
    return float(v[k])


def exp_gaps(n: int, mean: float, rng) -> np.ndarray:
    """n exponential gaps with the given mean, as the n quantiles of the
    distribution at the midpoints, in an order drawn from ``rng``: every
    seed gets the same multiset of gaps."""
    q = (np.arange(n) + 0.5) / n
    return rng.permutation(-np.log1p(-q) * mean)


def schedule(traffic: dict, n_sets: int, horizon: float):
    """Arrivals (due offset in seconds, set index) up to ``horizon``.

    Events come at ``rate / burst`` per second with exponential gaps,
    the first at the window's start; each brings ``burst`` requests (1:
    Poisson arrivals).  Sets come in equal shares.  The order of gaps
    and sets is drawn from the mix's own ``schedule_seed``: every run
    replays the same arrivals, and the run's seed draws the problems and
    the solver seeds."""
    rng = np.random.default_rng([int(traffic["schedule_seed"]), 11])
    burst = int(traffic.get("burst", 1))
    n_events = int(math.ceil(horizon * traffic["rate"] / burst)) + 1
    gaps = exp_gaps(n_events, burst / traffic["rate"], rng)
    due = np.concatenate([[0.0], np.cumsum(gaps[:-1])])
    n_req = n_events * burst
    sets = rng.permutation(np.arange(n_req) % n_sets)
    return [(float(due[i // burst]), int(sets[i])) for i in range(n_req)]


# --------------------------------------------------------- solo: closed
def closed_fit(run: Run) -> dict:
    """Back-to-back ``SaddleNuSVC.fit`` calls on one data set: the
    large-fit user.  The window ends at a fit boundary."""
    from repro.core.svm import SaddleNuSVC

    cfg, tr = run.cfg, run.traffic
    n1, n2, d = cfg["n1"], cfg["n2"], cfg["d"]
    with run.span("bench.data"):
        x, y = data.problem(run.seed, n1, n2, d, beta2=cfg["beta2"])
    nu = nu_of(cfg["alpha"], n1, n2)

    def fit(k):
        m = SaddleNuSVC(alpha=cfg["alpha"], eps=cfg["eps"],
                        beta=cfg["beta"], block_size=cfg["block_size"],
                        num_iters=cfg["num_iters"],
                        seed=solver_seed(run.seed, k))
        with run.span("bench.fit"):
            m.fit(x, y)
        # block steps the solve ran: its last history mark
        return (m.w_, m.b_, m.objective_), int(m.history_[-1][0])

    with run.span("bench.warm"):
        fit(0)
    answers, times, steps = [], [], []
    t_open = run.open_window()
    k = 1
    while True:
        if k == 1:
            run.trace_on()
        t = CLOCK()
        ans, st = fit(k)
        times.append(CLOCK() - t)
        answers.append(ans)
        steps.append(st)
        if k == tr["trace_fits"]:
            run.trace_off()
        k += 1
        if CLOCK() - t_open >= run.seconds:
            break
    t_close = CLOCK()
    run.trace_off()
    run.close_window()
    n_tr = min(tr["trace_fits"], len(answers))
    run.counters.update(
        fits_traced=n_tr, steps_traced=sum(steps[:n_tr]),
        fit_wall_traced_s=sum(times[:n_tr]))
    problems = [(x, y, nu)] * len(answers)
    return {"e2e": {"fit_s": (t_close - t_open) / len(answers)},
            "attempted": len(answers), "failed": 0,
            "answers": list(zip(problems, answers)),
            "notes": {"fits": len(answers), "steps_per_fit": steps[0],
                      "fit_s_each": times}}


# ------------------------------------------------- service: open loop
def _pool(run: Run, sets: list[dict]):
    """``pool`` problems per set, made from the seed in set-up."""
    k = run.traffic["pool"]
    with run.span("bench.data"):
        return [[data.problem(run.seed, s["n1"], s["n2"], s["d"], i, j,
                              beta2=run.cfg["beta2"])
                 for j in range(k)] for i, s in enumerate(sets)]


def _fit_request(cfg, x, y, nu, seed, num_iters=None):
    from repro.serve.solver_service import FitRequest
    return FitRequest(x=x, y=y, nu=nu, eps=cfg["eps"], beta=cfg["beta"],
                      gap_tol=cfg["gap_tol"], seed=seed,
                      num_iters=num_iters,
                      block_size=cfg.get("block_size", 1))


def make_service(cfg: dict, chips: int):
    """The configuration's ``SolverService``: ``SolverService()`` where
    the configuration has no ``service`` object; else its lanes, chunk
    length and point-sharding threshold, on a mesh over the first
    ``chips`` devices, which has to be the mesh the object states."""
    from repro.serve.solver_service import SolverService

    spec = cfg.get("service")
    if spec is None:
        return SolverService()
    if spec["mesh_chips"] != chips:
        raise ValueError(f"the configuration's service spans "
                         f"{spec['mesh_chips']} chips, the cell {chips}")
    from repro.launch.mesh import make_test_mesh
    return SolverService(num_slots=spec["num_slots"],
                         chunk_steps=spec["chunk_steps"],
                         mesh=make_test_mesh(chips),
                         shard_points_above=spec["shard_points_above"],
                         shard_num_slots=spec["shard_num_slots"])


def _window_work(T: float, counted, done: dict, start: dict) -> float:
    """The window's work in fits: a fit started at a and finished at e
    did the share (T - a) / (e - a) of its work by T."""
    return sum(1.0 if done[r][0] <= T
               else (T - start[r]) / (done[r][0] - start[r])
               for r in counted if r in done and r in start)


class StepLog:
    """Where the host's time goes in the window of a service cell: the
    wall time of every ``SolverService.step`` call, and for the slowest
    calls what else happened inside them (the process's CPU time, the
    times it was preempted, Python's garbage collection, JAX's own
    tracing and compiling events), so that a stall can be told apart
    from slow work."""

    KEEP = 5

    def __init__(self, run: Run, t_open: float):
        self.run, self.t_open = run, t_open
        self.walls: list[float] = []
        self.slow: list[dict] = []
        self.gc_s = {0: 0.0, 1: 0.0, 2: 0.0}
        self.gc_n = {0: 0, 1: 0, 2: 0}
        self.gc_max_s = 0.0
        self._gc_t = None
        gc.callbacks.append(self._gc)

    def _gc(self, phase, info):
        if phase == "start":
            self._gc_t = CLOCK()
        elif self._gc_t is not None:
            dt = CLOCK() - self._gc_t
            g = info["generation"]
            self.gc_s[g] += dt
            self.gc_n[g] += 1
            self.gc_max_s = max(self.gc_max_s, dt)
            self._gc_t = None

    def _mark(self):
        ru = resource.getrusage(resource.RUSAGE_SELF)
        ev = sum(v[1] for v in self.run.jax_events.values())
        return (CLOCK(), time.process_time(), ru.ru_nivcsw,
                sum(self.gc_s.values()), ev)

    @contextlib.contextmanager
    def step(self, info: dict):
        a = self._mark()
        yield
        b = self._mark()
        wall = b[0] - a[0]
        self.walls.append(wall)
        if len(self.slow) < self.KEEP or wall > self.slow[-1]["ms"] / 1e3:
            # ``info`` is the caller's, filled in after the call
            self.slow.append(dict(
                at_s=round(a[0] - self.t_open, 3), ms=1e3 * wall,
                cpu_ms=1e3 * (b[1] - a[1]), preempted=b[2] - a[2],
                gc_ms=1e3 * (b[3] - a[3]), jax_event_ms=1e3 * (b[4] - a[4]),
                info=info))
            self.slow.sort(key=lambda r: -r["ms"])
            del self.slow[self.KEEP:]

    def close(self) -> dict:
        gc.callbacks.remove(self._gc)
        w = np.asarray(self.walls or [0.0]) * 1e3
        return {"step_ms": {"calls": len(self.walls),
                            "p50": float(np.percentile(w, 50)),
                            "p99": float(np.percentile(w, 99)),
                            "max": float(w.max())},
                "slowest_steps": [dict(r, **r.pop("info"))
                                  for r in self.slow],
                "gc_in_window": {"collections": self.gc_n,
                                 "ms": {k: 1e3 * v
                                        for k, v in self.gc_s.items()},
                                 "max_ms": 1e3 * self.gc_max_s},
                "jax_events_in_window": self.run.jax_events}


def open_loop(run: Run) -> dict:
    """Open-loop arrivals into ``SolverService``: independent tenants
    that do not wait for each other.

    A latency mix (``judge: latency``) times each request from its due
    time to its result, and follows the requests due in the window to
    their results, arrivals going on, for up to ``drain_s`` after it.
    A throughput mix (``judge: throughput``) counts the work of the
    window in fits: a fit admitted before the window's end counts by the
    share of its time in a lane that falls inside the window, so the
    fits in flight at the end count by what they did there; they are
    followed to their results for up to ``follow_s``.  Arrivals are
    scheduled ``drain_s`` past the window."""
    cfg, tr = run.cfg, run.traffic
    sets = [cfg["sets"][name] for name in tr["sets"]]
    pool = _pool(run, sets)
    nus = [nu_of(cfg["alpha"], s["n1"], s["n2"]) for s in sets]
    svc = make_service(cfg, run.cell["chips"])

    with run.span("bench.warm"):
        for i, s in enumerate(sets):
            x, y = pool[i][0]
            svc.submit(_fit_request(cfg, x, y, nus[i], 1,
                                    num_iters=tr["warm_iters"]))
        svc.run()

    horizon = run.seconds + tr["drain_s"] + 5.0
    arrivals = schedule(tr, len(sets), horizon)
    judge_tail = tr["judge"] == "latency"
    limit = run.seconds + (tr["drain_s"] if judge_tail else tr["follow_s"])
    due, done, problem, admitted = {}, {}, {}, {}
    failed_ids = set()
    lateness = []
    nxt = 0
    traced = False
    marks = []                  # backlog every 10 s of the window
    t_open = run.open_window()
    log = StepLog(run, t_open)
    while True:
        now = CLOCK() - t_open
        if now >= 10.0 * (len(marks) + 1) and now < run.seconds + 1:
            marks.append(len(due) - len(done))
        if not traced and now >= run.seconds - tr["trace_s"]:
            # the window's last seconds; stopping the profiler stalls
            # the host, so that falls after the window
            run.trace_on()
            traced, t_on = True, CLOCK()
        tracing = run.tracer is not None and run.tracer.running
        if (tracing and now >= run.seconds
                and CLOCK() - t_on >= tr["trace_s"]):
            run.trace_off()
            tracing = False
        submitted = 0
        while nxt < len(arrivals) and arrivals[nxt][0] <= now:
            at, i = arrivals[nxt]
            x, y = pool[i][nxt % len(pool[i])]
            with run.span("bench.submit"):
                rid = svc.submit(_fit_request(
                    cfg, x, y, nus[i], solver_seed(run.seed, nxt)))
            due[rid] = at
            problem[rid] = (x, y, nus[i])
            lateness.append(CLOCK() - t_open - at)
            nxt += 1
            submitted += 1
            now = CLOCK() - t_open
        open_ids = [r for r in due if r not in done and r not in failed_ids]
        if judge_tail:
            followed = [r for r in due if due[r] < run.seconds]
        else:
            followed = [r for r in admitted if admitted[r] < run.seconds]
        if now >= run.seconds and not tracing and all(
                r in done or r in failed_ids for r in followed):
            break
        if now >= limit:
            break
        if not open_ids:
            wait = (arrivals[nxt][0] - now) if nxt < len(arrivals) else 0
            time.sleep(max(min(wait, 0.05), 0))
            continue
        t_call = CLOCK() - t_open
        info = {"submitted": submitted}
        with log.step(info), run.span("bench.step"):
            out = svc.step()
        t_done = CLOCK() - t_open
        for r in out:
            done[r.request_id] = (t_done, r)
            admitted.setdefault(r.request_id, t_call)
        info["done"] = len(out)
        info["admitted"] = 0
        for r in open_ids:
            if r in done:
                continue
            status = svc.status(r).name
            if status == "RUNNING" and r not in admitted:
                admitted[r] = t_call
                info["admitted"] += 1
            elif status in ("FAILED", "CANCELLED", "DEADLINE_EXCEEDED"):
                failed_ids.add(r)
    run.trace_off()
    run.close_window()
    notes = log.close()

    T = run.seconds
    if judge_tail:
        counted = [r for r in due if due[r] < T]
    else:
        counted = [r for r in admitted if admitted[r] < T]
    missing = [r for r in counted if r not in done]
    lat_ms = [1e3 * (done[r][0] - due[r]) for r in counted if r in done]
    work = _window_work(T, counted, done, admitted)
    e2e = {"fits_per_s": work / T}
    tails = {}
    if lat_ms:
        for q in (50, 70, 80, 90, 95, 99):
            tails[f"latency_p{q}_ms"] = percentile(lat_ms, q)
    for name in run.cell_metric_names:
        q = percentile_of(name)
        if q is not None:
            # a request that never came counts as missing every limit
            e2e[name] = (percentile(lat_ms + [math.inf] * len(missing), q)
                         if lat_ms else math.inf)
    answers = [(problem[r], (done[r][1].w, done[r][1].b,
                             done[r][1].objective))
               for r in counted if r in done]
    fits_by_T = sum(1 for r in done if done[r][0] <= T)
    run.counters["fits_in_window"] = fits_by_T
    notes.update({
        "submitted": len(due), "due_in_window": sum(
            1 for r in due if due[r] < T),
        "completed": len(done), "completed_by_window_end": fits_by_T,
        "window_work_fits": work,
        "backlog_at_end": len(due) - len(done),
        "generator_late_ms_max": 1e3 * max(lateness or [0.0]),
        "generator_late_ms_mean": 1e3 * float(np.mean(lateness or [0])),
        "backlog_every_10s": marks,
        "iterations_mean": float(np.mean(
            [done[r][1].iterations for r in done] or [0]))})
    notes.update({k: round(v, 3) for k, v in tails.items()})
    return {"e2e": e2e, "attempted": len(counted),
            "failed": len(missing) + len(failed_ids & set(counted)),
            "answers": answers, "notes": notes}


# ------------------------------------------- service: closed loop
def closed_service(run: Run) -> dict:
    """``clients`` callers, each keeping one fit in flight in
    ``SolverService``: the user of a group of large fits.  When a
    caller's result comes back from ``step()`` it submits its next fit
    at once, to the next problem of a ``pool`` made from the seed, with
    a new solver seed each time.  The callers go on until the run ends.

    The window's work in fits: a fit submitted before the window's end
    counts by the share of its time from the start of its submit to its
    result that falls inside the window, and is followed to its result
    for up to ``follow_s``.  A fit's time starts at its submit because
    ``submit`` runs the program's intake (the class split, the copy to
    the device, Algorithm 1's transform), which a caller waits for; from
    admission on, the fits of a group finish together, so lane time
    alone would count whole groups.  A traced run traces from the first
    ``step()`` at or after ``trace_s`` before the window's end until
    ``trace_s`` have passed and two chunks have run."""
    import jax

    cfg, tr = run.cfg, run.traffic
    n1, n2, d = cfg["n1"], cfg["n2"], cfg["d"]
    nu = nu_of(cfg["alpha"], n1, n2)
    with run.span("bench.data"):
        problems = [data.problem(run.seed, n1, n2, d, j,
                                 beta2=cfg["beta2"]) + (nu,)
                    for j in range(tr["pool"])]
    svc = make_service(cfg, run.cell["chips"])
    clients = tr["clients"]

    with run.span("bench.warm"):
        # one chunk each fills every lane of the group once
        for c in range(clients):
            x, y, _ = problems[c % len(problems)]
            svc.submit(_fit_request(
                cfg, x, y, nu, c,
                num_iters=svc.chunk_steps * cfg["block_size"]))
        svc.run()

    T = run.seconds
    done, problem, start = {}, {}, {}
    failed_ids = set()
    in_flight = []
    submit_s = []
    trace = {"on": None, "chunks": 0, "done": run.tracer is None}
    t_open = run.open_window()
    log = StepLog(run, t_open)

    def submit():
        k = len(problem)
        prob = problems[k % len(problems)]
        t = CLOCK()
        with run.span("bench.submit"):
            rid = svc.submit(_fit_request(cfg, prob[0], prob[1], nu,
                                          solver_seed(run.seed, k)))
        submit_s.append(CLOCK() - t)
        problem[rid] = prob
        start[rid] = t - t_open
        in_flight.append(rid)

    for _ in range(clients):
        submit()
    while True:
        if not trace["done"] and trace["on"] is None \
                and CLOCK() - t_open >= T - tr["trace_s"]:
            run.trace_on()
            trace["on"] = CLOCK()
        info = {"in_flight": len(in_flight)}
        with log.step(info), run.span("bench.step"):
            out = svc.step()
        t_done = CLOCK() - t_open
        if trace["on"] is not None and not trace["done"]:
            trace["chunks"] += 1
            if trace["chunks"] >= 2 and \
                    CLOCK() - trace["on"] >= tr["trace_s"]:
                run.trace_off()
                trace["done"] = True
        for r in out:
            done[r.request_id] = (t_done, r)
        ended = {r.request_id for r in out}
        for r in in_flight:
            if r not in ended and svc.status(r).name in (
                    "FAILED", "CANCELLED", "DEADLINE_EXCEEDED"):
                failed_ids.add(r)
                ended.add(r)
        info.update(done=len(out), ended=len(ended))
        in_flight[:] = [r for r in in_flight if r not in ended]
        # the run ends once every fit started in the window has ended,
        # before the callers' next submits
        now = CLOCK() - t_open
        if now >= T and trace["done"] and all(
                r in done or r in failed_ids for r in start
                if start[r] < T):
            break
        if now >= T + tr["follow_s"]:
            break
        for _ in ended:
            submit()
    run.trace_off()
    run.close_window()
    notes = log.close()
    notes["peak_bytes_by_device"] = {
        str(dv.id): (dv.memory_stats() or {}).get("peak_bytes_in_use")
        for dv in jax.devices()[:run.cell["chips"]]}

    counted = [r for r in start if start[r] < T]
    missing = [r for r in counted if r not in done]
    work = _window_work(T, counted, done, start)
    fits_by_T = sum(1 for r in done if done[r][0] <= T)
    notes.update({
        "submitted": len(problem), "completed": len(done),
        "completed_by_window_end": fits_by_T, "window_work_fits": work,
        "submit_s_mean": float(np.mean(submit_s)),
        # the intake's time in the window: split, copy, transform
        "submit_s_in_window": sum(min(s, T - start[r]) for r, s in
                                  zip(start, submit_s) if start[r] < T),
        "iterations_mean": float(np.mean(
            [done[r][1].iterations for r in done] or [0]))})
    answers = [(problem[r], (done[r][1].w, done[r][1].b,
                             done[r][1].objective))
               for r in counted if r in done]
    return {"e2e": {"fits_per_s": work / T}, "attempted": len(counted),
            "failed": len(missing),
            "answers": answers, "notes": notes}


MODES = {"closed_fit": closed_fit, "open_loop": open_loop,
         "closed_service": closed_service}
