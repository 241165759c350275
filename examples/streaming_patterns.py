"""Streaming quickstart: ingest batches, refresh incrementally, serve
queries between (and during) refreshes.

    PYTHONPATH=src python examples/streaming_patterns.py
"""
from repro.compile_cache import enable_compile_cache
from repro.core.streaming import PatternServer, StreamingMiner
from repro.data.transactions import load


def main():
    db, prof = load("retail", seed=0)
    init, stream = db[:10000], db[10000:]

    # fraction-based threshold: it rises as the database grows, so the
    # frequent border moves both ways (births AND deaths)
    miner = StreamingMiner(prof.n_items, prof.support, initial_db=init,
                           n_workers=4, max_k=5)
    server = PatternServer(miner)

    rep = miner.refresh()
    print(f"gen {rep.generation}: {rep.frequent} frequent itemsets "
          f"over {rep.n_transactions} transactions "
          f"({rep.wall_s:.2f}s from scratch)")

    step = len(stream) // 4
    for i in range(4):
        batch = stream[i * step:(i + 1) * step]
        ing = miner.ingest(batch)
        print(f"  ingested {ing.n_transactions} tx as segment "
              f"{ing.segment} ({ing.payload_bytes} B packed)")
        # queries keep answering from the published generation —
        # ingest never blocks them, refresh never blocks them
        hot = server.top_k((), 3)
        print(f"  serving gen {server.snapshot.generation}, top-3 "
              f"{hot}")
        rep = miner.refresh()
        print(f"gen {rep.generation}: {rep.frequent} frequent | "
              f"border +{rep.born}/-{rep.died} | candidates: "
              f"{rep.reused} reused, {rep.swept_delta} delta-swept, "
              f"{rep.swept_full} fully swept | {rep.rows_touched} "
              f"rows in {rep.wall_s:.2f}s")

    itemset = server.top_k((), 1)[0][0]
    print(f"support{itemset} = {server.support(itemset)} "
          f"at generation {server.snapshot.generation}")


if __name__ == "__main__":
    enable_compile_cache()
    main()
