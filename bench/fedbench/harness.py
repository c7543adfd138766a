"""One run of one cell: set-up, the measured window, the untimed finish to
the commit of the window's round, the comparison with the reference.

The window opens at the scheduler pass the traffic names
(``window_opens``) and closes at the end of the first ``client.post``
span that ends at least ``seconds`` after it opened, so it always holds
whole silo updates; it overruns ``seconds`` by less than one update.
``update_s`` is its length over the updates posted inside it.
"""
from __future__ import annotations

import gc
import sys
import time
from types import SimpleNamespace

from fedbench import checks, device, spec
from fedbench import trace as tr
from fedbench.data import SiloStream
from fedbench.reference import Reference, weights_key

MAX_SETUP_PASSES = 64
WINDOW_CAP_S = 240.0


def log(msg: str):
    print(msg, file=sys.stderr, flush=True)


def _span_rows(fed, lo: float, hi: float) -> list:
    """Closed spans of the run that overlap ``[lo, hi]``."""
    return [SimpleNamespace(name=s.name, actor=s.actor, t0=s.t0, t1=s.t1,
                            attrs=dict(s.attrs or {}))
            for s in fed.tel.spans(fed.run_id, include_open=False)
            if s.t1 >= lo and s.t0 <= hi]


def _batches_in(fed, lo: float, hi: float, train_spans) -> tuple:
    """(training, evaluation) batches served inside the window."""
    n_train = n_eval = 0
    for stream in fed.streams:
        for t, _ in stream.calls:
            if not lo <= t <= hi:
                continue
            if any(s.t0 <= t <= s.t1 for s in train_spans):
                n_train += 1
            else:
                n_eval += 1
    return n_train, n_eval


def reference_batches(cfg: dict, traffic: dict, seed: int, calls: dict,
                      rounds) -> list:
    """Per round, per silo, the batches the program's training drew."""
    fed = cfg["federation"]
    streams = [SiloStream(org, i, seed=seed, vocab=cfg["model"]["vocab"],
                          seq_len=fed["seq_len"], alpha=traffic["alpha"])
               for i, org in enumerate(fed["organizations"])]
    return [[[s.tokens(k, fed["batch_size"]) for k in calls[(i, r)]]
             for i, s in enumerate(streams)] for r in rounds]


def run_cell(cell: spec.Cell, seed: int, seconds: float, trace: bool, *,
             devices, t_start: float) -> dict:
    from fedbench.federation import Federation

    stats = device.CompileStats()
    ref = Reference(cell.config)
    params = ref.init_params(weights_key(seed))
    fed = Federation(cell.config, cell.traffic, seed, params)
    fed.warm(params)
    del params
    opens = cell.traffic["window_opens"]
    for _ in range(MAX_SETUP_PASSES):
        if fed.at(opens):
            break
        fed.step()
    else:
        raise RuntimeError(f"the run never reached {opens}")
    cap = tr.Capture() if trace else None
    if cap is not None:
        cap.start()
    set_up = stats.count("backend_compile"), stats.count("cache_hits")

    t_open = time.perf_counter()
    t_close, passes = None, 0
    while t_close is None:
        fed.step()
        passes += 1
        late = [s for s in fed.posts() if s.t1 >= t_open + seconds]
        if late:
            t_close = late[0].t1
        elif time.perf_counter() - t_open > WINDOW_CAP_S:
            raise RuntimeError(f"no silo update within {WINDOW_CAP_S} s")
    if cap is not None:
        cap.stop()
    window_posts = [s for s in fed.posts() if t_open < s.t1 <= t_close]
    in_window = stats.within(t_open, t_close)
    last_round = max(s.attrs["round"] for s in window_posts)
    fed.commit(last_round)
    mem = device.memory_peak_bytes(devices)
    log(f"window: {t_close - t_open:.6f} s over {passes} passes, "
        f"{len(window_posts)} silo updates; set-up {t_open - t_start:.6f} s "
        f"with {set_up[0]} compiles and {set_up[1]} cache loads; "
        f"{in_window} compiles or cache loads inside the window")

    spans = _span_rows(fed, t_open, t_close)
    train_spans = [s for s in spans if s.name == "client.train"]
    n_train, n_eval = _batches_in(fed, t_open, t_close, train_spans)
    dev = device.describe(devices)
    ctx = SimpleNamespace(
        lo=t_open, hi=t_close, window_s=t_close - t_open,
        n_updates=len(window_posts), spans=spans,
        train_batches=n_train, eval_batches=n_eval,
        config=cell.config, traffic=cell.traffic, t=fed.t,
        cohort=len(fed.orgs), chips=dev["count"],
        peaks=device.peaks(dev["kind"]) if dev["platform"] == "tpu" else None,
        trace=cap)
    prog = fed.results()
    committed = {h for h in prog["rounds"]}
    failed = sum(1 for s in window_posts if s.attrs["round"] not in committed)
    del fed
    gc.collect()

    batches = reference_batches(cell.config, cell.traffic, seed,
                                prog["calls"], prog["rounds"])
    t_ref = time.perf_counter()
    want = ref.follow(seed, batches)
    log(f"reference: {time.perf_counter() - t_ref:.6f} s for "
        f"{len(prog['rounds'])} rounds")
    numbers = checks.compare(prog, want,
                             checks.kept_leaves(want["grad_norms"]))
    log("readings: " + "; ".join(
        f"{k} {v!r}" + (f" at {numbers['where'][k]}"
                        if k in numbers["where"] else "")
        for k, v in numbers.items() if k != "where"))
    numbers["window_compiles"] = in_window
    limits = dict(cell.limits(), window_compiles=0)
    verdict = checks.judge(numbers, limits)

    metrics = {}
    if trace:
        for m in cell.per_layer:
            value = spec.reader(m["name"])(ctx)
            if value is not None:
                metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    else:
        e2e = {"update_s": ctx.window_s / ctx.n_updates,
               "setup_s": t_open - t_start}
        for m in cell.end_to_end:
            metrics[m["name"]] = {"value": e2e[m["name"]], "unit": m["unit"]}
    dev["memory_peak_bytes"] = mem
    out = {"correct": checks.passed(verdict) and failed == 0,
           "attempted": len(window_posts), "failed": failed,
           "metrics": metrics, "device": dev}
    if cap is not None:
        lo, hi = t_open + cap.offset, t_close + cap.offset
        dev["busy_s"] = tr.busy_s(cap.events, lo, hi)
        dev["window_s"] = hi - lo
        host_spans = [(s.name, s.t0 + cap.offset, s.t1 + cap.offset)
                      for s in spans]
        out["breakdown"] = {
            "device_ops": tr.top_ops(cap.events, lo, hi),
            "idle_gaps": tr.idle_gaps(cap.events, lo, hi, host_spans)}
        log("trace planes: " + "; ".join(
            f"{k}: {', '.join(v[:8])}" for k, v in cap.summary.items()))
        log(f"trace programs: {tr.top_ops(cap.events, lo, hi, 12, 'modules')}")
    out["checks"] = verdict
    for name, c in verdict.items():
        log(f"check {name}: {c['value']!r} (limit {c['limit']!r})")
    return out
