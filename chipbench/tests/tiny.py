"""Cells cut to sizes a CPU test run can hold, with the broken paths the
run tests plant under them."""
from chipbench import run

CONFIGS = {
    "pile_vocab17": dict(run.load_config("pile_vocab17"), n_tokens=1 << 14,
                         sigma=512, shard_bits=10),
    # a 4-letter alphabet, so that some patterns occur across seams
    "pizzachili_bytes": dict(run.load_config("pizzachili_bytes"), sigma=4,
                             n_tokens=1 << 14, shard_bits=10),
}
TRAFFIC = {
    "pile_vocab17.ingest": dict(run.load_traffic("ingest"),
                                shards_per_chip=4, chunks=2),
    "pile_vocab17.audit": dict(run.load_traffic("audit"), rate_per_s=20.0,
                               buckets=[8, 32], answer_wait_s=3.0,
                               verify_sample=32),
    "pizzachili_bytes.search": dict(run.load_traffic("search"), pool=256,
                                    batches=8, batch=32, verify_sample=32),
}


# The query cell is not declared in BENCHMARK.json while its p95 spreads
# wider than any bound can hold (PERF.md); its driver is tested under the
# entries it would have.
BENCH = run.load_bench()
BENCH["workloads"].append({"name": "pile_vocab17.audit",
                           "config": "pile_vocab17", "traffic": "audit",
                           "chips": 1})
BENCH["end_to_end"].append({"name": "query_p95_ms", "unit": "ms",
                            "workloads": ["pile_vocab17.audit"]})


def run_tiny(cell, cache_dir, hooks=None, seconds=1.0, seed=2 ** 35 + 11):
    return run.run_cell(cell, seed, seconds, False, bench=BENCH,
                        config=CONFIGS[cell.split(".")[0]],
                        traffic=TRAFFIC[cell], hooks=hooks,
                        require_tpu=False, cache_dir=cache_dir)
