"""Open loop: Poisson arrivals at a fixed rate through ``ServingTier``.

Set-up makes the initial records on the device from the seed, builds the
index as the configuration states (positions on, capacity reserved for
inserts), registers one tenant with the configuration's settings, and
warms the flush shapes the traffic meets: one flush of each request
count up to ``warm_flush_requests``, one request of each power-of-two
bucket size up to ``max_batch`` and, where the mix inserts, two inserts.  The tier's own flusher thread then runs.

The window sends each request at its scheduled time from this one
thread: a scan is one ``index`` op through ``ServingTier.submit``, an
insert one value through ``ServingTier.append``.  A scan's latency runs
from its scheduled send time to the moment its ticket completes; a scan
that is refused counts as failed, with the latency of the whole wait.
After the window every ticket is awaited (a minute at most), and where
the mix inserts, every inserted record is read back through the same
path once the inserts have been flushed.

The check frees the program's state, rebuilds the column (initial
records, then the warm-up and window inserts in the order they were
acknowledged) and compares every position answered with the plain
reference's leftmost argmin.
"""

from __future__ import annotations

import gc
import threading
import time

import numpy as np

from rmqbench import data
from rmqbench.reference import RangeMinRef, mismatches

TENANT = "ycsb"
WAIT_S = 60.0


class State:
    def __init__(self, tier, stream, appended):
        self.tier = tier
        self.stream = stream
        self.appended = appended      # values acknowledged, in order


def _tenant_settings(cfg):
    t = cfg["tenant"]
    return {"slo_ms": t["slo_ms"], "max_batch": t["max_batch"],
            "max_queue": t["max_queue"]}


def setup(run):
    import jax

    from repro.core import RMQ
    from repro.serving import ServingTier

    cfg, mix = run.config, run.traffic
    records, capacity = int(cfg["recordcount"]), int(cfg["capacity"])
    x = data.device_uniform(run.seed, records)
    index = RMQ.build(x, c=cfg["c"], t=cfg["t"],
                      with_positions=cfg["with_positions"],
                      capacity=capacity)
    jax.block_until_ready(index.hierarchy.upper)
    del x
    settings = _tenant_settings(cfg)
    tier = ServingTier()
    tier.register_tenant(TENANT, index, **settings)
    del index
    stream = data.request_stream(dict(mix, seconds=run.seconds), records,
                                 run.seed)
    # warm the flush shapes the traffic meets: every flush of 1 to
    # ``warm_flush_requests`` single-scan requests, then one request of
    # each power-of-two count of scans up to ``max_batch``
    warm = data.rng(run.seed, 4)
    span = int(mix["max_scan_length"])

    def flush(sizes):
        tickets = []
        for k in sizes:
            ls = warm.integers(0, records - span, k).astype(np.int32)
            rs = ls + warm.integers(0, span, k).astype(np.int32)
            tickets.append(tier.submit(TENANT, ls, rs, "index"))
        tier.drain(TENANT)
        for tk in tickets:
            tk.result(timeout=WAIT_S)

    for m in range(1, int(mix["warm_flush_requests"]) + 1):
        flush([1] * m)
    k = 16
    while k <= settings["max_batch"]:
        flush([k])
        k *= 2
    appended = []
    if float(mix["insert_share"]) > 0:
        for _ in range(2):
            v = warm.random(1, dtype=np.float32)
            tier.append(TENANT, v)
            appended.append(float(v[0]))
            tier.drain(TENANT)
    tier.start()
    return State(tier, stream, appended)


def window(run, st):
    tier, s = st.tier, st.stream
    count = s["at"].shape[0]
    done = np.full(count, np.nan)
    sent = np.full(count, np.nan)
    refused = np.zeros(count, bool)
    errored = np.zeros(count, bool)
    tickets = {}
    lock = threading.Lock()

    def completer(i):
        def cb(_fut):
            t = time.monotonic()
            with lock:
                done[i] = t
        return cb

    trace_from = float(run.traffic["trace_from_s"])
    trace_min = int(run.traffic["trace_requests"])
    traced_at = None
    t0 = time.monotonic() + 0.01
    due = t0 + s["at"]
    for i in range(count):
        wait = due[i] - time.monotonic()
        if wait > 0:
            time.sleep(wait)
        if run.trace and traced_at is None and s["at"][i] >= trace_from:
            run.trace_start()
            traced_at = i
        elif traced_at is not None and run.tracing and \
                i - traced_at >= trace_min:
            run.trace_stop()
        sent[i] = time.monotonic()
        if s["insert"][i]:
            with run.span("insert"):
                tier.append(TENANT, s["values"][i:i + 1])
            st.appended.append(float(s["values"][i]))
            done[i] = time.monotonic()
            continue
        try:
            with run.span("submit"):
                tk = tier.submit(TENANT, s["ls"][i:i + 1],
                                 s["rs"][i:i + 1], "index")
        except Exception as e:            # refused: counts as failed
            refused[i] = True
            run.log(f"request {i} refused: {e}")
            continue
        tickets[i] = tk
        tk.future.add_done_callback(completer(i))
    run.trace_stop()
    deadline = time.monotonic() + WAIT_S
    answers = {}
    for i, tk in tickets.items():
        try:
            answers[i] = tk.result(timeout=max(deadline - time.monotonic(),
                                               0.001))
        except Exception as e:            # no answer, or an error
            errored[i] = True
            run.log(f"request {i} failed: {e}")
    gave_up = time.monotonic()
    failed = refused | errored
    close = t0 + run.seconds
    with lock:
        # due by the close and not answered by it, unsent ones included
        backlog = int(np.count_nonzero(
            (due <= close) & ~failed & ~(done <= close)))
    scans = ~s["insert"]
    with lock:
        lat = np.where(np.isnan(done), gave_up, done) - due
    lat = np.where(failed, gave_up - due, lat)
    scan_lat = lat[scans]
    lag = sent - due
    summary = {
        "requests": count, "scans": int(scans.sum()),
        "inserts": int(s["insert"].sum()),
        "refused": int(refused.sum()),
        "errored": int(errored.sum()),
        "backlog_at_close": backlog,
        "p50_ms": float(np.percentile(scan_lat, 50) * 1e3),
        "p99_ms": float(np.percentile(scan_lat, 99) * 1e3),
        "lag_p50_ms": float(np.percentile(lag, 50) * 1e3),
        "lag_p99_ms": float(np.percentile(lag, 99) * 1e3),
        "lag_max_ms": float(np.max(lag) * 1e3),
        "rate_per_s": float(run.traffic["rate_per_s"]),
    }
    return {
        "end_to_end": {"p99_ms": summary["p99_ms"]},
        "indexed_bytes": int(run.config["recordcount"]) * 4,
        "attempted": count,
        "failed": int(failed.sum()),
        "answers": answers,
        "errored": int(errored.sum()),
        "summary": summary,
    }


def _read_back(run, st, record):
    """Every inserted record, read back through the tier once flushed."""
    records = int(run.config["recordcount"])
    inserted = len(st.appended)
    if not inserted:
        return None
    st.tier.drain(TENANT)
    pos = np.arange(records, records + inserted, dtype=np.int32)
    # each insert alone, and a span from the last initial record over it
    ls = np.concatenate([pos, np.full(inserted, records - 1, np.int32)])
    rs = np.concatenate([pos, pos])
    cap = int(run.config["tenant"]["max_batch"])
    tickets = [st.tier.submit(TENANT, ls[a:a + cap], rs[a:a + cap], "index")
               for a in range(0, ls.shape[0], cap)]
    got, gens = [], []
    for tk in tickets:
        try:
            got.append(np.asarray(tk.result(timeout=WAIT_S)))
        except Exception as e:           # unanswerable: counted as lost
            run.log(f"read-back failed: {e}")
            got.append(np.full(tk.count, -1, np.int32))
        gens.append(tk.generation)
    return ls, rs, np.concatenate(got), gens


def check(run, st, record):
    import jax

    cfg = run.config
    records = int(cfg["recordcount"])
    back = _read_back(run, st, record)
    st.tier.stop()
    st.tier = None
    gc.collect()
    x = data.device_uniform(run.seed, records)
    col = np.concatenate([data.host_copy(x),
                          np.asarray(st.appended, np.float32)])
    del x
    ref = RangeMinRef(col, block=256)
    s = st.stream
    idx = np.array(sorted(record["answers"]), np.int64)
    got = [np.asarray(a).reshape(-1) for a in
           jax.device_get([record["answers"][i] for i in idx])]
    got = np.concatenate(got) if got else np.zeros(0, np.int32)
    _, want = ref.query(s["ls"][idx], s["rs"][idx])
    wrong = mismatches(got, want)
    checks = [{"name": "wrong_positions", "value": wrong, "limit": 0},
              {"name": "unanswered", "value": record["errored"],
               "limit": 0}]
    if back is not None:
        ls, rs, got_b, gens = back
        _, want_b = ref.query(ls, rs)
        lost = mismatches(got_b, want_b)
        stale = sum(1 for g in gens if g != len(st.appended))
        checks += [{"name": "inserts_not_read_back", "value": lost,
                    "limit": 0},
                   {"name": "stale_generation", "value": stale, "limit": 0}]
    run.log(f"check: {idx.size} scans"
            + (f", {len(st.appended)} inserts read back" if back else ""))
    return checks
