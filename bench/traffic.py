"""One general traffic generator, driven by a mix file
(``bench/traffic/<mix>.json``), and the open loop that replays it.

The open loop (copied from the program's ``serving/loadgen.py`` and fixed
here): arrivals are scheduled up front (Poisson at ``rate_qps``) and
submitted at their instants whatever completes; latency runs from the
*scheduled* arrival, so a stall is charged to the server.  Two gaps of
the original are closed: every send records how late it left against
its schedule, and Count and Range have widths of their own.

The work is the same for every seed: the requests of a window (kinds,
centres, widths, clients) and the multiset of gaps between arrivals are
drawn from the configuration's ``structure_seed`` and the request count;
``--seed`` shuffles the order of both, and draws the rows themselves.
So seeds differ in arrivals and rows, not in how heavy the window is.

Centres: ``data_frac`` of them are data rows, the rest uniform over the
domain.  With ``zipf_a`` set, data centres are drawn by Zipf rank over
a permutation fixed by ``structure_seed`` (rows are generated in sorted
order, so rank r lands in the same region for every seed); without it
they are uniform over the rows.
"""
from __future__ import annotations

import dataclasses
import time

import numpy as np

KINDS = ("count", "range", "point", "knn")


@dataclasses.dataclass
class Request:
    """One scheduled submission: its kind, payload arrays and schedule."""

    t: float                 # scheduled send, seconds after the loop starts
    client: str
    kind: str
    lo: np.ndarray = None    # (m, d) windows (count, range)
    hi: np.ndarray = None
    xs: np.ndarray = None    # (m, d) points or kNN centres
    k: int = 0
    metric: str = "l2"

    def query(self):
        """The program's typed query for this request."""
        from repro.api import Count, Knn, Point, Range
        if self.kind == "count":
            return Count(self.lo, self.hi)
        if self.kind == "range":
            return Range(self.lo, self.hi)
        if self.kind == "point":
            return Point(self.xs)
        return Knn(self.xs, k=self.k, metric=self.metric)

    @property
    def size(self) -> int:
        return len(self.lo if self.lo is not None else self.xs)


class Generator:
    """Draws requests of one mix over one table of rows."""

    def __init__(self, mix: dict, data: np.ndarray, K: int,
                 structure_seed: int):
        self.mix = mix
        self.data = data
        self.K = K
        self.domain = float(2**K - 1)
        kinds = mix["mix"]
        self.kinds = [k for k in KINDS if kinds.get(k, 0) > 0]
        self.p = np.array([kinds[k] for k in self.kinds], dtype=float)
        if not np.isclose(self.p.sum(), 1.0):
            raise ValueError(f"kind mix sums to {self.p.sum()}, not 1")
        self.p /= self.p.sum()
        c = mix["centers"]
        self.data_frac = float(c["data_frac"])
        self.zipf_a = c.get("zipf_a")
        self.structure_seed = int(structure_seed)
        self.perm = np.random.default_rng(structure_seed).permutation(
            len(data)) if self.zipf_a else None
        self.m = int(mix.get("windows_per_request", 1))

    def centers(self, rng, n: int) -> np.ndarray:
        """(n, d) float64 centres: data rows (Zipf or uniform) or uniform
        points of the domain."""
        d = self.data.shape[1]
        out = rng.uniform(0, self.domain, size=(n, d))
        on = rng.random(n) < self.data_frac
        k = int(on.sum())
        if self.zipf_a:
            rank = (rng.zipf(float(self.zipf_a), size=k) - 1) % len(self.data)
            idx = self.perm[rank]
        else:
            idx = rng.integers(0, len(self.data), size=k)
        out[on] = self.data[idx].astype(np.float64)
        return out

    def request(self, rng, kind: str, t: float, client: str) -> Request:
        m, d = self.m, self.data.shape[1]
        if kind in ("count", "range"):
            c = self.centers(rng, m)
            w = rng.uniform(0, float(self.mix[f"{kind}_width"]) * self.domain,
                            size=(m, d))
            lo = np.clip(c - w / 2, 0, self.domain).astype(np.uint64)
            hi = np.clip(c + w / 2, 0, self.domain).astype(np.uint64)
            return Request(t, client, kind, lo=lo, hi=hi)
        if kind == "point":
            if self.zipf_a:
                rank = (rng.zipf(float(self.zipf_a), size=m) - 1) \
                    % len(self.data)
                xs = self.data[self.perm[rank]]
            else:
                xs = self.data[rng.integers(0, len(self.data), size=m)]
            xs = xs.copy()
            absent = rng.random(m) >= float(self.mix["point_present_frac"])
            xs[absent, 0] ^= np.uint64(1)
            return Request(t, client, kind, xs=xs)
        knn = self.mix["knn"]
        xs = self.centers(rng, m).astype(np.uint64)
        return Request(t, client, kind, xs=xs, k=int(knn["k"]),
                       metric=knn.get("metric", "l2"))

    # -- open loop -----------------------------------------------------
    def schedule(self, seed_seq, seconds: float, rate: float,
                 stream: int = 0) -> list:
        """``rate x seconds`` requests over `seconds`, as Requests.

        The requests and the gaps between them come from the structure
        seed, the count and `stream`; the seed shuffles both.  Arrival
        times are the shuffled gaps' running sum scaled to the window (a
        Poisson process conditioned on its count).  Another `stream`
        gives another draw of the same mix (the warm-up's)."""
        n = max(1, int(round(rate * seconds)))
        fixed = np.random.default_rng([self.structure_seed, n, stream])
        gaps = fixed.exponential(1.0, size=n + 1)
        kinds = fixed.choice(len(self.kinds), size=n, p=self.p)
        clients = fixed.integers(0, int(self.mix["n_clients"]), size=n)
        reqs = [self.request(fixed, self.kinds[k], 0.0, f"c{c}")
                for k, c in zip(kinds, clients)]
        rng = np.random.default_rng(seed_seq)
        gaps = gaps[rng.permutation(n + 1)]
        times = np.cumsum(gaps)[:n] / gaps.sum() * seconds
        return [dataclasses.replace(reqs[j], t=float(t))
                for j, t in zip(rng.permutation(n), times)]


@dataclasses.dataclass
class Sent:
    """One request as the loop sent it."""

    req: Request
    t_due: float             # loop clock the latency is measured from
    late_s: float            # how late the send left against its schedule
    ticket: object = None    # None: shed by admission control
    error: str = None


def run_open_loop(server, schedule: list, clock=time.perf_counter) -> tuple:
    """Submit each request at its instant (never waiting on completions);
    returns (sends, t0)."""
    from repro.serving import ServerOverloaded
    t0 = clock()
    sent = []
    for r in schedule:
        due = t0 + r.t
        while True:
            dt = due - clock()
            if dt <= 0:
                break
            time.sleep(min(dt, 0.002))
        late = clock() - due
        try:
            ticket = server.submit(r.query(), client=r.client)
        except ServerOverloaded:
            ticket = None
        sent.append(Sent(r, due, late, ticket))
    return sent, t0


def collect(sent: list, deadline_s: float = 60.0) -> None:
    """Wait for every admitted ticket, at most `deadline_s` in all past
    now; a ticket that errors or never resolves keeps its error."""
    end = time.perf_counter() + deadline_s
    for s in sent:
        if s.ticket is None:
            continue
        try:
            s.ticket.result(timeout=max(0.0, end - time.perf_counter()))
        except Exception as e:
            s.error = repr(e)
