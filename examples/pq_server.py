"""Serving example: parallel-combining scheduler over a real decode model.

Concurrent client sessions submit prompts with deadlines; the async PC
scheduler (DESIGN.md §3 — dedicated combiner loop + the §9 sharded
batched-PQ deadline ordering) combines them into dense decode batches —
one device program per combining pass instead of one per request.  The
"pc-async" row uses the non-blocking ``submit_async`` future API.

Run:  PYTHONPATH=src python examples/pq_server.py --sessions 8
"""
import argparse

from repro.launch.serve import run_serving


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen2_0_5b")
    ap.add_argument("--sessions", type=int, default=8)
    ap.add_argument("--requests", type=int, default=3)
    ap.add_argument("--tokens", type=int, default=8)
    ap.add_argument("--max-batch", type=int, default=8)
    a = ap.parse_args()

    print(f"[pq_server] {a.sessions} sessions × {a.requests} requests, "
          f"{a.tokens} tokens each (reduced {a.arch})")
    for sched in ("serial", "pc", "pc-async"):
        stats = run_serving(a.arch, sessions=a.sessions,
                            requests_per_session=a.requests,
                            n_tokens=a.tokens, max_batch=a.max_batch,
                            scheduler=sched, seed=0, reduced=True)
        print(f"  {sched:8s}: {stats['req_per_s']:7.2f} req/s  "
              f"{stats['device_steps']:4d} device dispatches  "
              f"mean batch {stats['mean_batch']}")
    print("  -> combining serves the same requests in a fraction of the "
          "device dispatches (the paper's free-cycles claim)")


if __name__ == "__main__":
    main()
