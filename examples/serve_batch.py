"""End-to-end serving driver: the train→deploy→predict loop through the
managed inference subsystem (src/repro/serving/).

Trains a tiny model through the control plane, deploys it as an
inference endpoint (an LCM job with a continuous-batching engine),
streams concurrent predict requests at it — finished sequences retire
and queued requests join mid-flight into freed KV-cache slots — then
prints the endpoint stats and drains it.

  PYTHONPATH=src python examples/serve_batch.py --arch stablelm-1.6b-smoke \
      --requests 8 --capacity 3 --max-new 8
"""
import argparse
import sys
import tempfile
import threading
import time
from pathlib import Path

sys.path.insert(0, str(Path(__file__).resolve().parents[1] / "src"))

import numpy as np  # noqa: E402

from repro.service.core import DLaaSCore  # noqa: E402

MANIFEST = """name: serve-batch-src
learners: 1
gpus: 1
steps: {steps}
batch_docs: 2
checkpoint_every: 100
data:
  n_docs: 32
  seq_len: 16
framework:
  name: repro-lm
  arch: {arch}
"""


def wait_state(core, eid, want, timeout=300.0):
    t0 = time.time()
    while time.time() - t0 < timeout:
        st = core.endpoint_status(eid)
        if st["state"] == want:
            return st
        if st["state"] == "FAILED":
            raise SystemExit(f"endpoint {eid} FAILED "
                             f"(job {st['job_state']})")
        time.sleep(0.05)
    raise SystemExit(f"endpoint {eid} never reached {want} "
                     f"within {timeout:.0f}s")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="stablelm-1.6b-smoke")
    ap.add_argument("--requests", type=int, default=8)
    ap.add_argument("--capacity", type=int, default=3)
    ap.add_argument("--prompt-len", type=int, default=12)
    ap.add_argument("--max-new", type=int, default=8)
    ap.add_argument("--train-steps", type=int, default=3)
    args = ap.parse_args()

    core = DLaaSCore(tempfile.mkdtemp(prefix="serve_batch_"),
                     tick_interval=0.005)
    try:
        # 1) train through the platform (weights land in the results
        #    store — the same object the endpoint will load)
        print(f"== training {args.arch} ({args.train_steps} steps) ==")
        mid = core.deploy_model(MANIFEST.format(
            arch=args.arch, steps=args.train_steps))["model_id"]
        tid = core.create_training(mid)["training_id"]
        st = core.wait_for(tid, timeout=300)
        print(f"training {tid}: {st}")
        if st != "COMPLETED":
            raise SystemExit(f"training failed: {st}")

        # 2) deploy: the endpoint is an LCM job (queued, placed,
        #    metered); DEPLOYING covers weight download + jit build
        out = core.deploy_endpoint(
            from_training=tid, capacity=args.capacity,
            max_new=args.max_new, max_queue=max(16, args.requests))
        eid = out["endpoint_id"]
        print(f"== deployed {eid} from {tid} ==")
        wait_state(core, eid, "READY")
        print("endpoint READY")

        # 3) stream concurrent predicts: more requests than slots, so
        #    late requests join mid-flight as earlier ones retire
        rng = np.random.RandomState(0)
        prompts = [rng.randint(0, 100, size=args.prompt_len)
                   for _ in range(args.requests)]
        results = [None] * args.requests
        t0 = time.time()

        def client(i):
            results[i] = core.predict(eid, prompts[i],
                                      max_new=args.max_new)

        threads = [threading.Thread(target=client, args=(i,))
                   for i in range(args.requests)]
        [t.start() for t in threads]
        [t.join() for t in threads]
        wall = time.time() - t0
        for i, r in enumerate(results):
            toks = r["tokens"]
            print(f"req {i}: {len(toks)} tokens in {r['latency_s']}s: "
                  f"{toks[:8]}{'...' if len(toks) > 8 else ''}")

        # 4) stats + drain
        stats = core.endpoint_status(eid)["stats"]
        print(f"== served {stats['completed_total']} requests in "
              f"{wall:.2f}s ({stats['completed_total'] / wall:.1f} req/s, "
              f"{stats['tokens_out_total']} tokens) ==")
        print(f"   occupancy={stats['mean_batch_occupancy']} over "
              f"{stats['decode_steps']} decode steps; "
              f"p50={stats['p50_latency_s']}s "
              f"p99={stats['p99_latency_s']}s; "
              f"rejected={stats['rejected_total']}")
        core.stop_endpoint(eid)
        wait_state(core, eid, "STOPPED", timeout=60.0)
        print(f"endpoint drained and STOPPED; final stats snapshot "
              f"kept, KV buffers released")
    finally:
        core.close()


if __name__ == "__main__":
    main()
