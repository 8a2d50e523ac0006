"""Async serving demo: many client threads submit typed queries against
one `AsyncServer` while the SLO-driven drain loop coalesces them into
engine super-batches — and every served answer is bit-identical to
serial `Database.query` execution.

    PYTHONPATH=src python examples/serve_async.py
"""
import threading

import numpy as np

from repro.api import Count, Database, Knn, Point, Range
from repro.compile_cache import use_compile_cache
from repro.core.theta import default_K
from repro.data.synth import make_dataset
from repro.data.workload import make_workload
from repro.serving import SLOConfig, assert_bit_identical, replay_serial

N_CLIENTS = 8
PER_CLIENT = 20


def main():
    use_compile_cache()
    print("== async serving demo ==")
    data = make_dataset("osm", 20_000, seed=0)
    K = default_K(2)
    Ls, Us = make_workload(data, 64, seed=1, K=K)
    db = Database.fit(data, (Ls, Us), K=K, learn=False)
    print(db)

    slo = SLOConfig(p99_target_ms=50.0, max_queue=512, overload="reject",
                    window_init_ms=2.0, window_max_ms=25.0)
    collected = {}

    def client(name, seed):
        rng = np.random.default_rng(seed)
        got = []
        for _ in range(PER_CLIENT):
            j = int(rng.integers(0, len(Ls)))
            q = rng.choice([Count(Ls[j:j + 1], Us[j:j + 1]),
                            Range(Ls[j:j + 1], Us[j:j + 1]),
                            Point(data[j:j + 1]),
                            Knn(data[j:j + 1], k=4, metric="l2")])
            got.append(srv.submit(q, client=name))
        collected[name] = [(t, t.result(timeout=30)) for t in got]

    with db.serve(slo=slo) as srv:      # or router.serve(...) over shards
        threads = [threading.Thread(target=client, args=(f"c{i}", 100 + i))
                   for i in range(N_CLIENTS)]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        stats = srv.stats()

    print(f"served {stats['served']} submissions from {N_CLIENTS} threads "
          f"in {stats['batches']} coalesced batches "
          f"(session ran {stats['session_batches']} engine super-batches); "
          f"final window {stats['controller']['window_ms']:.2f} ms, "
          f"p99 {stats['controller']['p99_ms']:.2f} ms")

    # the audit: replay the server's admission-ordered query log serially
    # and compare every served result, bit for bit
    oracle = replay_serial(db, srv.query_log())
    for name, pairs in collected.items():
        for ticket, res in pairs:
            assert_bit_identical(res, oracle[ticket.seq],
                                 context=f"{name}/seq{ticket.seq}")
    print(f"exactness: {sum(len(p) for p in collected.values())} served "
          f"results bit-identical to serial replay ✓")


if __name__ == "__main__":
    main()
