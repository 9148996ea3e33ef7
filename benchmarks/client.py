"""The load generator and the stream clients of a serving cell.

One scheduler (the caller's thread) sends each request of a plan when it
is due, whatever became of the earlier ones (open loop); each request is
one thread that reads its stream to the end, as `chip_smoke.Streams`
does.  Every latency is taken from when the request was DUE, so a stall
counts against the requests that waited behind it, and how late the
scheduler itself ran is recorded.

Knows nothing of ray_tpu: `stream_fn(request)` yields the stream's items
(`{"tokens": [...]}`), so the tests drive it with a fake.
"""

from __future__ import annotations

import threading
import time
from typing import Any, Callable, Dict, Iterable, List, Optional


class Record:
    """What the client saw of one request; times on `time.monotonic`."""

    __slots__ = ("rid", "counted", "prompt_len", "want", "due", "sent",
                 "first", "last", "tokens", "items", "error", "done",
                 "cancelled")

    def __init__(self, req: Dict[str, Any], due: float):
        self.rid = req["rid"]
        self.counted = bool(req["counted"])
        self.prompt_len = len(req["tokens"])
        self.want = int(req["max_new_tokens"])
        self.due = due
        self.sent = self.first = self.last = None
        self.tokens: List[int] = []
        self.items: List[tuple] = []   # (arrival, tokens in the item)
        self.error: Optional[str] = None
        self.done = False
        self.cancelled = False

    def as_dict(self) -> Dict[str, Any]:
        return {k: getattr(self, k) for k in self.__slots__
                if k not in ("tokens", "items")} | {
                    "n_tokens": len(self.tokens)}


def _read_stream(rec: Record, req: Dict[str, Any],
                 stream_fn: Callable[[Dict[str, Any]], Iterable[dict]],
                 stop: threading.Event) -> None:
    stream = None
    try:
        rec.sent = time.monotonic()
        stream = stream_fn({"tokens": req["tokens"],
                            "request_id": req["rid"],
                            "max_new_tokens": req["max_new_tokens"]})
        for item in stream:
            now = time.monotonic()
            if rec.first is None:
                rec.first = now
            rec.last = now
            rec.tokens.extend(item["tokens"])
            rec.items.append((now, len(item["tokens"])))
            if stop.is_set():
                break
        # a stream that the window's end cut short is dropped, not done
        if stop.is_set() and len(rec.tokens) != rec.want:
            rec.cancelled = True
        else:
            rec.done = True
    except Exception as e:  # recorded: a failed request is a result
        rec.error = f"{type(e).__name__}: {e}"
    finally:
        if hasattr(stream, "close"):
            stream.close()   # a dropped stream releases its replica slot


def run_open_loop(plan: Dict[str, Any],
                  stream_fn: Callable[[Dict[str, Any]], Iterable[dict]],
                  *, end: str, drain_s: float,
                  cancel_fn: Optional[Callable[[List[str]], Any]] = None,
                  on_window: Optional[Callable[[float, float], Any]] = None
                  ) -> Dict[str, Any]:
    """Send the plan's requests on schedule and follow them to the end.

    `end`: "drain" — after the window wait up to `drain_s` for the open
    streams; "cancel" — drop them at the window's end (`cancel_fn` ends
    them in the engine).  Either way every stream still open after that
    is cancelled and its thread joined.  `on_window(w0, w1)` is called,
    on a thread of its own, when the window starts, with its bounds on
    `time.monotonic`.

    Returns {"w0", "w1" (monotonic), "w0_epoch", "records": [Record]}.
    """
    if end not in ("drain", "cancel"):
        raise ValueError(f"end must be 'drain' or 'cancel', not {end!r}")
    stop = threading.Event()
    t0 = time.monotonic()
    epoch0 = time.time()
    w0 = t0 + plan["lead_in_s"]
    w1 = w0 + plan["window_s"]
    side = None
    if on_window is not None:
        def at_window():
            time.sleep(max(0.0, w0 - time.monotonic()))
            on_window(w0, w1)

        side = threading.Thread(target=at_window, daemon=True,
                                name="bench-window")
        side.start()
    records, threads = [], []
    for req in plan["requests"]:
        due = t0 + req["due_s"]
        delay = due - time.monotonic()
        if delay > 0:
            time.sleep(delay)
        rec = Record(req, due)
        t = threading.Thread(target=_read_stream,
                             args=(rec, req, stream_fn, stop),
                             daemon=True, name=f"bench-{req['rid']}")
        records.append(rec)
        threads.append(t)
        t.start()
    time.sleep(max(0.0, w1 - time.monotonic()))
    if end == "drain":
        deadline = w1 + drain_s
        for t in threads:
            t.join(max(0.0, deadline - time.monotonic()))
    stop.set()
    open_rids = [r.rid for r, t in zip(records, threads) if t.is_alive()]
    if open_rids and cancel_fn is not None:
        cancel_fn(open_rids)
    deadline = time.monotonic() + 30.0
    for t in threads:
        t.join(max(0.0, deadline - time.monotonic()))
    hung = [r.rid for r, t in zip(records, threads) if t.is_alive()]
    if side is not None:
        side.join(60.0)
    return {"w0": w0, "w1": w1, "w0_epoch": epoch0 + (w0 - t0),
            "records": records, "open_at_end": len(open_rids),
            "hung": hung}


# ------------------------------------------------------------- reduction


def summarize(run: Dict[str, Any], vocab_size: int, end: str
              ) -> Dict[str, Any]:
    """From the records to the samples and counts the metrics are made
    of.  A counted request (due inside the window) has FAILED when its
    stream raised, when it holds a token outside the vocabulary, when it
    finished with another number of tokens than it asked for, and — in a
    cell that drains — when it did not finish in time.  In a cell that
    cancels, a stream dropped at the window's end is neither."""
    from benchmarks.stats import tpot_s

    w0, w1 = run["w0"], run["w1"]
    counted = [r for r in run["records"] if r.counted]
    failed, ttft, tpot, late = [], [], [], []
    for r in counted:
        bad = (r.error is not None
               or any(not 0 <= t < vocab_size for t in r.tokens)
               or (r.done and len(r.tokens) != r.want)
               or (not r.done and (end == "drain" or not r.cancelled)))
        if bad:
            failed.append(r.rid)
            continue
        late.append(r.sent - r.due)
        if r.first is not None:
            ttft.append(r.first - r.due)
        if r.done:
            x = tpot_s(r.first, r.last, len(r.tokens))
            if x is not None:
                tpot.append(x)
    tokens_in_window = sum(n for r in run["records"]
                           for t, n in r.items if w0 <= t < w1)
    return {"attempted": len(counted), "failed": len(failed),
            "failed_rids": failed[:20], "ttft_s": ttft, "tpot_s": tpot,
            "late_s": late, "tokens_in_window": tokens_in_window,
            # tokens a request holds in the cache half-way through its
            # answer, mean over the window's requests
            "mean_context": (sum(r.prompt_len + r.want / 2.0
                                 for r in counted) / len(counted)
                             if counted else None),
            "window_s": w1 - w0,
            "finished": sum(1 for r in counted if r.done),
            "open_at_end": run["open_at_end"], "hung": run["hung"]}
