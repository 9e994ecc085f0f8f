"""What decides ``correct``.

Two parts. The window's own answers are judged one by one, exactly: every
update call answers the number of rows it carried, every read one finite
score per row and live label, no call fails, and the servers' coalescers
counted every row that was acknowledged. The model that the timed path
builds is then judged against the configuration's plain reference by a
*check plan* (the traffic file's ``check.steps``), driven through the same
server processes, RPC entry, coalescers and compiled programs right after
the window, at the window's own load: as many connections in closed
loops, calls of the window's size, so that the coalescer joins them into
the flushes the window was timed on.

Why a plan after ``clear`` and not the window's own model: every flush is
decided against the model before it, and which calls share a flush under
64 concurrent connections is a race that no reference can replay (an
answer does not even say when its flush ran: the device step is enqueued,
not waited for). The plan's steps are such that no race decides their
outcome: a lone call is a flush of its own; calls sent at once share no
column of the hashed space, so any grouping of them gives the same model;
reads change nothing. What the race does decide, the size of the flushes,
the plan reads back and holds to the traffic file's numbers."""

from __future__ import annotations

import math
import threading
import time

import numpy as np
from typing import Any, Callable, Dict, List, Optional, Sequence, Tuple

from .loadgen import Client, burst

CHECK_STREAM = 1000      # seed streams of the check's rows start here


class Compared:
    """Each number compared, beside its limit."""

    def __init__(self) -> None:
        self.rows: List[Tuple[str, float, float]] = []

    def add(self, name: str, value: float, limit: float) -> None:
        self.rows.append((name, float(value), float(limit)))

    @property
    def correct(self) -> bool:
        return all(math.isfinite(v) and v <= lim for _n, v, lim in self.rows)

    def as_dict(self) -> Dict[str, Dict[str, float]]:
        return {n: {"value": v, "limit": lim} for n, v, lim in self.rows}

    def lines(self) -> List[str]:
        return [f"compared {n}: {v!r} (limit {lim!r})"
                + ("" if math.isfinite(v) and v <= lim else "  <-- FAILS")
                for n, v, lim in self.rows]


def judge_window(records: Sequence[tuple], groups: Dict[str, Dict[str, Any]],
                 engine: Any, data: Dict[str, Any]) -> Tuple[int, int]:
    """(update answers with the wrong count, read answers malformed) over
    every answered call of the run's stream."""
    acks_wrong = 0
    malformed = 0
    for rec in records:
        g = groups[rec[0]]
        if not rec[6]:
            continue  # failed calls are counted as failed
        ok = engine.well_formed(g["method"], rec[7], g["rows_per_call"], data)
        if g["method"] == engine.UPDATE:
            acks_wrong += not ok
        else:
            malformed += not ok
    return acks_wrong, malformed


class Subject:
    """What is checked: a configuration, its engine's RPC surface
    (``engines/<engine>.py``), its plain reference
    (``references/<reference>.py``) and the generator of its rows
    (``generators/<data.generator>.py``), at one width."""

    def __init__(self, config: Dict[str, Any], engine: Any, ref: Any,
                 generator: Any, dim: int) -> None:
        self.config = config
        self.engine = engine
        self.ref = ref
        self.generator = generator
        self.data = config["data"]
        self.featurize = ref.Featurizer(config["model"]["converter"], dim)

    def batch(self, rows: Sequence[Any]) -> Any:
        return self.ref.Batch(rows, self.featurize)

    def model(self, universe: np.ndarray, precision: str) -> Any:
        return self.ref.Model(universe, self.config["model"],
                              self.data["labels"], precision)

    def request(self, method: str, name: str, rows: List[Any]) -> bytearray:
        return self.engine.ENCODERS[method](name, rows)

    def rows(self, seed: int, stream: int, n: int,
             key_suffix: str = "") -> List[Any]:
        return self.generator.make_rows(self.data, seed, stream, n,
                                        key_suffix)


class WindowScores:
    """The window's own ``classify`` answers against the reference, where
    the traffic lets the reference follow the model: one connection trains
    (so its calls are flushes, in the order sent) and nothing else does.

    A sample of the answers kept whole, drawn from the seed, with the one
    that waited longest in it. An answer does not say which model it saw,
    but the order of things bounds it: it saw every train call that was
    acknowledged before it was sent (an acknowledged call has been put to
    the device, and the device keeps the order), and no call that was sent
    after it was answered. The reference is taken at every state between,
    and the nearest one counts."""

    def __init__(self, subject: Subject, records: Sequence[tuple],
                 groups: Dict[str, Dict[str, Any]],
                 pool_rows: Dict[str, List[List[Any]]],
                 warm_rows: Sequence[tuple], t0: float, t1: float,
                 server: int, seed: int, sample: int) -> None:
        self.subject = subject
        update, read = subject.engine.UPDATE, subject.engine.READ
        mine = [rec for rec in records if groups[rec[0]]["server"] == server]
        trains = sorted((rec for rec in mine
                         if groups[rec[0]]["method"] == update),
                        key=lambda rec: rec[4])
        self.sound = all(rec[6] for rec in trains) and len(
            {rec[1] for rec in trains}) <= 1
        kept = [rec for rec in mine if rec[8] is not None
                and groups[rec[0]]["method"] == read
                and t0 <= rec[5] < t1]
        rng = np.random.default_rng([int(seed), 4242])
        picked = [kept[i] for i in sorted(rng.choice(
            len(kept), size=min(sample, len(kept)), replace=False))] \
            if kept else []
        if kept:
            longest = max(kept, key=lambda rec: rec[5] - rec[3])
            if longest not in picked:
                picked.append(longest)
        # the flushes, in order: the lone warm-up train calls, then the
        # stream's, each a batch of the pool (featurized once)
        cache: Dict[Tuple[str, int], Any] = {}

        def pooled(group: str, index: int) -> Any:
            key = (groups[group]["name"], index)
            if key not in cache:
                cache[key] = subject.batch(pool_rows[key[0]][index])
            return cache[key]

        self.flushes = [subject.batch(rows)
                        for method, rows in warm_rows if method == update] \
            + [pooled(rec[0], rec[2]) for rec in trains]
        n_warm = len(self.flushes) - len(trains)
        acked = sorted(rec[5] for rec in trains)
        sent = [rec[4] for rec in trains]
        self.answers = []          # (first state, last state, batch, result)
        for rec in picked:
            lo = n_warm + int(np.searchsorted(acked, rec[4], side="left"))
            hi = n_warm + int(np.searchsorted(sent, rec[5], side="right"))
            self.answers.append((lo, hi, pooled(rec[0], rec[2]), rec[8]))
        self.universe = subject.ref.universe_of(
            self.flushes + [a[2] for a in self.answers])

    def states(self, precision: str) -> List[Dict[int, List[Dict[str, float]]]]:
        """For every sampled answer, the reference's {label: score} rows at
        each state it may have seen, {state: rows}."""
        model = self.subject.model(self.universe, precision)
        out: List[Dict[int, List[Dict[str, float]]]] = [
            {} for _ in self.answers]
        last = max([a[1] for a in self.answers] + [0])
        for state in range(min(last, len(self.flushes)) + 1):
            if state:
                model.train_flush(self.flushes[state - 1])
            for k, (lo, hi, batch, _res) in enumerate(self.answers):
                if lo <= state <= hi:
                    out[k][state] = self.subject.ref.label_scores(
                        model, batch)
        return out

    def gap(self, precision: str = "float32",
            control: Optional[str] = None) -> Tuple[float, Dict[str, Any]]:
        """The widest gap of a sampled answer from the nearest state it may
        have seen, over the largest reference score. With ``control`` the
        answers judged are not the program's but the reference's in that
        precision, at the first state of each bracket."""
        if not self.sound or not self.answers:
            return math.inf, {"answers": 0}
        want = self.states(precision)
        got = [a[3] for a in self.answers]
        if control is not None:
            low = self.states(control)
            got = [[[[lb, sc] for lb, sc in row.items()]
                    for row in st[min(st)]] for st in low]
        widest, scale, n = 0.0, 0.0, 0
        for result, by_state in zip(got, want):
            best = math.inf
            for rows in by_state.values():
                g = _rows_gap(result, rows)
                best = min(best, g)
                scale = max([scale] + [abs(v) for r in rows
                                       for v in r.values()])
            widest = max(widest, best)
            n += 1
        rel = widest / scale if scale > 0 and math.isfinite(widest) \
            else math.inf
        return rel, {"answers": n, "widest_abs": widest, "scale": scale,
                     "flushes": len(self.flushes)}


def _rows_gap(result: Any, want: List[Dict[str, float]]) -> float:
    """Widest |answered - wanted| over the rows of one answer; infinite
    where the shape or the labels differ."""
    if not isinstance(result, (list, tuple)) or len(result) != len(want):
        return math.inf
    gap = 0.0
    for got_row, want_row in zip(result, want):
        try:
            got = {str(lb): float(sc) for lb, sc in got_row}
        except (TypeError, ValueError):
            return math.inf
        if set(got) != set(want_row):
            return math.inf
        for lb, sc in want_row.items():
            gap = max(gap, abs(got[lb] - sc))
    return gap


def full_flushes(steps: Sequence[Tuple[int, int]], full_min: int,
                 full_rows: int) -> int:
    """How many flushes of the timed size a burst's samples saw. ``steps``
    are the samples in which the coalescer's ``flush_count`` rose, as
    (flushes, rows) gained since the sample before. A sample of ``f``
    flushes counts, as ``f``, where its rows lie between ``f`` times
    ``full_min`` and ``f`` times ``full_rows``, the sizes of the window's
    flushes as the traffic file gives them: no flush is larger than
    ``full_rows``, so at that mean none of them is far below ``full_min``.
    With ``full_min`` equal to ``full_rows`` only exact flushes count."""
    return sum(f for f, n in steps if f * full_min <= n <= f * full_rows)


class Plan:
    """Runs a check plan against the servers, and again on the reference."""

    def __init__(self, subject: Subject, steps: Sequence[Dict[str, Any]],
                 addresses: Sequence, name: str, seed: int,
                 status: Callable[[int], Dict[str, Any]],
                 log: Callable[[str], None]) -> None:
        self.subject = subject
        self.steps = list(steps)
        self.addresses = list(addresses)
        self.name = name
        self.seed = seed
        self.status = status
        self.log = log
        self._stream = CHECK_STREAM
        #: per attempt: what the reference has to mirror, in order
        self.script: List[Tuple[str, Any]] = []
        self.answers: List[Tuple[int, List[Any], Any]] = []
        self.plan_mismatch = 0
        self.failed = 0
        self.acks_wrong = 0
        #: rows of acknowledged update calls per server, over all attempts
        self.rows_acked = [0] * len(self.addresses)
        #: per server, the calls of the last ``train_burst`` step
        self.burst_calls: Dict[int, List[List[Any]]] = {}
        #: the script's and the answers' rows, featurized once
        self._batches: Dict[int, Any] = {}

    # -- rows ----------------------------------------------------------------
    def _rows(self, n: int) -> List[Any]:
        self._stream += 1
        return self.subject.rows(self.seed, self._stream, n)

    def _servers(self, step: Dict[str, Any]) -> List[int]:
        which = step.get("server", "each")
        return list(range(len(self.addresses))) if which == "each" \
            else [int(which)]

    def _each(self, servers: Sequence[int], fn: Callable[[int], None]) -> None:
        """``fn(server)`` on every listed server at once."""
        errors: List[BaseException] = []

        def guarded(i: int) -> None:
            try:
                fn(i)
            except BaseException as e:  # noqa: BLE001 — re-raised below
                errors.append(e)

        threads = [threading.Thread(target=guarded, args=(i,), daemon=True)
                   for i in servers]
        for t in threads:
            t.start()
        for t in threads:
            t.join(600)
        if errors:
            raise errors[0]

    def _counts(self, i: int, client: Optional[Client] = None
                ) -> Tuple[int, int]:
        """(flushes, rows) the update coalescer of server ``i`` has counted."""
        engine = self.subject.engine
        queue = f"microbatch.{engine.QUEUE[engine.UPDATE]}"
        st = self.status(i) if client is None else next(iter(
            client.call("get_status", self.name).values()))
        return (int(st.get(f"{queue}.flush_count", 0)),
                int(st.get(f"{queue}.item_count", 0)))

    def _watch(self, i: int, done: threading.Event,
               samples: List[Tuple[int, int]]) -> None:
        """Sample :meth:`_counts` of server ``i`` over one connection as
        fast as it answers, until ``done`` and once more."""
        with Client(self.addresses[i]) as c:
            while True:
                last = done.is_set()
                samples.append(self._counts(i, c))
                if last:
                    return
                time.sleep(0.004)

    def _update_frame(self, rows: List[Any]) -> bytearray:
        return self.subject.request(self.subject.engine.UPDATE, self.name,
                                    rows)

    # -- the program's side ------------------------------------------------------
    def run(self, attempts: int = 3) -> None:
        """The plan, again from its start while a step did not go as the
        reference replays it or as the traffic file wants it (a lone call
        that was not one flush, a burst whose flushes were not the timed
        size)."""
        for attempt in range(attempts):
            self.script, self.answers, self._batches = [], [], {}
            self.plan_mismatch = 0
            self._stream = CHECK_STREAM + 1000 * attempt
            for step in self.steps:
                getattr(self, "_do_" + step["op"])(step)
            if not self.plan_mismatch:
                return
            self.log(f"check: attempt {attempt + 1}: {self.plan_mismatch} "
                     "steps did not go as planned; again from the start")

    def _do_clear(self, step: Dict[str, Any]) -> None:
        def one(i: int) -> None:
            with Client(self.addresses[i]) as c:
                if c.call("clear", self.name) is not True:
                    self.failed += 1
        self._each(self._servers(step), one)
        self.script.append(("clear", self._servers(step)))

    def _ack(self, rec: Dict[str, Any], rows: int, server: int) -> None:
        if "error" in rec:
            self.failed += 1
            self.log(f"check: call failed: {rec['error']}")
            return
        self.rows_acked[server] += rows
        if rec["result"] != rows:
            self.acks_wrong += 1

    def _do_train(self, step: Dict[str, Any]) -> None:
        """``calls`` lone calls of ``rows`` rows, one after another on each
        server, every server at once; each call is one flush."""
        servers = self._servers(step)
        batches = {i: [self._rows(step["rows"]) for _ in range(step["calls"])]
                   for i in servers}

        def one(i: int) -> None:
            before = self._counts(i)[0]
            with Client(self.addresses[i]) as c:
                for rows in batches[i]:
                    rec: Dict[str, Any] = {}
                    try:
                        rec["result"] = c.call_frame(self._update_frame(rows))
                    except (OSError, RuntimeError, ValueError) as e:
                        rec["error"] = repr(e)
                    self._ack(rec, len(rows), i)
            self.plan_mismatch += abs(
                self._counts(i)[0] - before - step["calls"])
        self._each(servers, one)
        for i in servers:
            for rows in batches[i]:
                self.script.append(("flush", (i, rows)))

    def _disjoint_calls(self, calls: int, rows: int) -> List[List[Any]]:
        """``calls`` lists of ``rows`` rows such that no two lists share a
        column of the hashed space: every list has keys of its own, and a
        feature whose hash falls on a column that an earlier list uses is
        taken out of its row (at the cell's width under two in a hundred of
        192 calls' features, three in a hundred of the last call's)."""
        feat = self.subject.featurize
        taken: set = set()
        out: List[List[Any]] = []
        for k in range(calls):
            self._stream += 1
            mine: set = set()
            kept: List[Any] = []
            for label, strings, nums in self.subject.rows(
                    self.seed, self._stream, rows, key_suffix=f".{k}"):
                free_s, free_n = [], []
                for kv in strings:
                    cols = feat.string_columns(*kv)
                    if taken.isdisjoint(cols):
                        free_s.append(kv)
                        mine.update(cols)
                for kv in nums:
                    cols = feat.num_columns(kv[0])
                    if taken.isdisjoint(cols):
                        free_n.append(kv)
                        mine.update(cols)
                kept.append((label, free_s, free_n))
            taken |= mine
            out.append(kept)
        return out

    def _do_train_burst(self, step: Dict[str, Any]) -> None:
        """``calls`` calls of ``rows`` rows over ``connections`` connections
        in closed loops, as the window's calls come (``connections`` left
        out: a connection for each call): the coalescer joins them into
        flushes as it finds them, which no one can replay. So the calls
        are made to share no column of the hashed space
        (:meth:`_disjoint_calls`): a row's scores, step size and update
        touch its own columns only, and a call is never split between
        flushes, so whichever calls share a flush, and in whatever order
        the flushes go, the model that results is the same. The reference
        applies them one call a flush. Every label has to be live before
        the burst (a flush with one live label has no rival to step away
        from, and a label that becomes live in the burst is a rival from
        whichever flush carried it first), so a plan trains a lone call
        first.

        What the flushes were is read back from the coalescer's own
        counters, sampled all through the burst (:meth:`_watch`) and
        counted by :func:`full_flushes`. Fewer than ``min_full_flushes``
        of the timed size and the burst did not drive the timed shape: the
        plan is run again. (A burst has two or three short flushes while
        it starts, as the window's stream had before the window, and ends
        in single calls when the queue runs empty; they are compared with
        the rest.)"""
        servers = self._servers(step)
        calls = {i: self._disjoint_calls(step["calls"], step["rows"])
                 for i in servers}
        conns = int(step.get("connections", step["calls"]))
        full_rows = int(step.get("full_rows", 0))
        full_min = int(step.get("full_rows_min", full_rows))

        def one(i: int) -> None:
            frames = [self._update_frame(r) for r in calls[i]]
            done = threading.Event()
            samples: List[Tuple[int, int]] = []
            watcher = threading.Thread(target=self._watch, daemon=True,
                                       args=(i, done, samples))
            watcher.start()
            recs = burst(self.addresses[i], frames, conns)
            done.set()
            watcher.join(60)
            for rec, rows in zip(recs, calls[i]):
                self._ack(rec, len(rows), i)
            steps = [(f1 - f0, n1 - n0) for (f0, n0), (f1, n1)
                     in zip(samples, samples[1:]) if f1 != f0]
            full = full_flushes(steps, full_min, full_rows)
            self.log(f"check: server{i}: {len(frames)} calls over {conns} "
                     f"connections were {sum(f for f, _n in steps)} flushes "
                     f"of {sum(n for _f, n in steps)} rows in all, {full} "
                     f"of them of {full_min} to {full_rows} rows; as "
                     "sampled (flushes x rows): " + " ".join(
                         f"{f}x{n // f}" if n % f == 0 else f"{f}:{n}"
                         for f, n in steps))
            if full < step.get("min_full_flushes", 0):
                self.plan_mismatch += 1
        self._each(servers, one)
        self.burst_calls = calls
        for i in servers:
            for rows in calls[i]:
                self.script.append(("flush", (i, rows)))

    def _probes(self, step: Dict[str, Any], server: int,
                fresh: List[List[Any]]) -> List[List[Any]]:
        """The rows of a ``classify`` step's calls: ``calls`` calls of
        ``rows`` fresh rows and, with ``burst_rows``, that many rows of
        every call of the last burst, whose scores show what each call
        taught, in calls of ``rows`` rows at most."""
        n_old = int(step.get("burst_rows", 0))
        old = [r for call in self.burst_calls.get(server, [])
               for r in call[:n_old]]
        n = int(step["rows"])
        return fresh + [old[k:k + n] for k in range(0, len(old), n)]

    def _do_classify(self, step: Dict[str, Any]) -> None:
        """The step's probe calls on each server, at once on connections
        of their own: a read changes nothing, so how the query coalescer
        groups them cannot change an answer."""
        servers = self._servers(step)
        fresh = [self._rows(step["rows"]) for _ in range(step["calls"])]
        probes = {i: self._probes(step, i, fresh) for i in servers}
        read = self.subject.engine.READ

        def one(i: int) -> None:
            frames = [self.subject.request(read, self.name, r)
                      for r in probes[i]]
            recs = burst(self.addresses[i], frames)
            for rec, rows in zip(recs, probes[i]):
                if "error" in rec:
                    self.failed += 1
                    self.log(f"check: {read} failed: {rec['error']}")
                    continue
                self.answers.append((i, rows, rec["result"]))
        self._each(servers, one)
        self.script.append(("scores", sum(len(p) for p in probes.values())))

    # -- the reference's side ------------------------------------------------------
    def score_gap(self, precision: str = "float32",
                  answers: Optional[Sequence[Tuple[int, List[Any], Any]]] = None
                  ) -> Tuple[float, int, Dict[str, Any]]:
        """(widest gap between an answered score and the reference's, over
        the largest reference score; answers whose shape or labels are
        wrong; detail). With ``answers`` None the program's own answers
        are judged; the control passes the low-precision reference's."""
        answers = self.answers if answers is None else answers
        expected = self.replay(precision)
        gap = 0.0
        scale = 0.0
        wrong = 0
        n = 0
        for (_i, _rows, result), want in zip(answers, expected):
            g = _rows_gap(result, want)
            if math.isinf(g):
                wrong += 1
                continue
            gap = max(gap, g)
            scale = max([scale] + [abs(v) for r in want for v in r.values()])
            n += sum(len(r) for r in want)
        rel = gap / scale if scale > 0 else (0.0 if gap == 0 else math.inf)
        if n == 0:
            rel = math.inf
        return rel, wrong, {"scores": n, "widest_abs": gap, "scale": scale}

    def replay(self, precision: str) -> List[List[Dict[str, float]]]:
        """The script on the reference: for each read answer of the plan
        (in order) the expected {label: score} rows. The rows are
        featurized once, whatever the precisions replayed."""
        def batch(rows: List[Any]) -> Any:
            key = id(rows)
            if key not in self._batches:
                self._batches[key] = self.subject.batch(rows)
            return self._batches[key]

        for kind, arg in self.script:
            if kind == "flush":
                batch(arg[1])
        for _i, rows, _res in self.answers:
            batch(rows)
        ref = self.subject.ref
        universe = ref.universe_of(list(self._batches.values()))
        models = [self.subject.model(universe, precision)
                  for _ in self.addresses]
        expected: List[List[Dict[str, float]]] = []
        pending = list(self.answers)
        # answers were appended step by step; a "scores" entry consumes the
        # answers of that step. Failed reads are missing from the answers
        # (they are counted as failed), and the rest no longer line up
        lined_up = len(self.answers) == sum(
            arg for kind, arg in self.script if kind == "scores")
        for kind, arg in self.script:
            if kind == "clear":
                for i in arg:
                    models[i].clear()
            elif kind == "flush":
                models[arg[0]].train_flush(batch(arg[1]))
            elif kind == "scores" and lined_up:
                for _ in range(arg):
                    i, rows, _res = pending.pop(0)
                    expected.append(ref.label_scores(models[i], batch(rows)))
        return expected

    def control_answers(self, precision: str
                        ) -> List[Tuple[int, List[Any], Any]]:
        """The answers the reference would give in ``precision``, in the
        program's place: what the control is judged on."""
        expected = self.replay(precision)
        return [(i, rows, [[[lb, sc] for lb, sc in row.items()]
                           for row in want])
                for (i, rows, _res), want in zip(self.answers, expected)]
