"""Online-serving benchmark: QPS / latency / batch-fill / cache hit-rate
under synthetic multi-client load.

Load model: ``--clients`` threads each issue ``--requests`` node-ID
queries back-to-back (closed loop). Request sizes are uniform in
[1, --max-request]; ids follow a Zipf-ish skew (squared uniform, the
same concentration trick as examples.common.synthetic_products) so the
embedding cache sees realistic repeat traffic. ``--rpc`` routes clients
over the socket fabric instead of the in-process path, measuring the
full wire cost.

Prints one JSON line:
  qps, latency_p50_ms/p99_ms, batch_fill_ratio, cache_hit_rate,
  warmup_seconds, compile stats (to certify zero steady-state
  recompiles), and the config.

``GLT_BENCH_PLATFORM=cpu`` forces the CPU backend.
"""
import argparse
import json
import os
import sys
import threading
import time

sys.path.insert(0, os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))

import numpy as np


def main():
  ap = argparse.ArgumentParser()
  ap.add_argument('--num-nodes', type=int, default=24_000)
  ap.add_argument('--avg-degree', type=int, default=25)
  ap.add_argument('--feat-dim', type=int, default=100)
  ap.add_argument('--hidden', type=int, default=256)
  ap.add_argument('--classes', type=int, default=47)
  ap.add_argument('--fanout', default='10,5')
  ap.add_argument('--buckets', default='8,32,128')
  ap.add_argument('--clients', type=int, default=4)
  ap.add_argument('--requests', type=int, default=50,
                  help='requests per client')
  ap.add_argument('--max-request', type=int, default=16,
                  help='max node ids per request')
  ap.add_argument('--max-wait-ms', type=float, default=2.0)
  ap.add_argument('--cache-capacity', type=int, default=50_000)
  ap.add_argument('--zipf-skew', type=float, default=2.0,
                  help='uniform^skew id concentration (higher = hotter)')
  ap.add_argument('--rpc', action='store_true',
                  help='clients go over the socket fabric')
  args = ap.parse_args()

  from glt_tpu.utils.backend import force_backend
  force_backend()
  import jax

  from examples.common import synthetic_products
  from glt_tpu.models import GraphSAGE
  from glt_tpu.serving import InferenceEngine, ServingClient, \
      ServingServer

  fanout = [int(x) for x in args.fanout.split(',')]
  buckets = [int(x) for x in args.buckets.split(',')]
  ds, num_classes = synthetic_products(
      num_nodes=args.num_nodes, avg_degree=args.avg_degree,
      feat_dim=args.feat_dim, num_classes=args.classes)
  model = GraphSAGE(hidden_features=args.hidden,
                    out_features=num_classes, num_layers=len(fanout))

  engine = InferenceEngine(ds, model, None, fanout, buckets=buckets,
                           cache_capacity=args.cache_capacity)
  # fresh weights: serving cost is invariant to the trained values
  engine.init_params(jax.random.key(0))

  t0 = time.perf_counter()
  srv = ServingServer(engine, max_wait_ms=args.max_wait_ms,
                      request_timeout_ms=120_000.0)
  warmup_s = time.perf_counter() - t0
  compile_after_warmup = engine.compile_stats()

  def client(rank: int, errors: list):
    rng = np.random.default_rng(rank)
    cli = ServingClient(*srv.address) if args.rpc else srv
    try:
      for _ in range(args.requests):
        n = int(rng.integers(1, args.max_request + 1))
        ids = ((rng.random(n) ** args.zipf_skew)
               * args.num_nodes).astype(np.int64)
        out = cli.infer(ids)
        assert out.shape[0] == n
    except BaseException as e:  # noqa: BLE001 — surfaced in the report
      errors.append(f'client {rank}: {e!r}')
    finally:
      if args.rpc:
        cli.close()

  errors: list = []
  t0 = time.perf_counter()
  threads = [threading.Thread(target=client, args=(r, errors))
             for r in range(args.clients)]
  for t in threads:
    t.start()
  for t in threads:
    t.join()
  load_s = time.perf_counter() - t0

  snap = srv.metrics.snapshot(cache=engine.cache)
  compile_end = engine.compile_stats()
  srv.close()

  report = {
      'bench': 'serving',
      'transport': 'rpc' if args.rpc else 'inproc',
      'clients': args.clients,
      'requests': snap['requests'],
      'qps': round(snap['requests'] / load_s, 2),
      'ids_per_sec': round(snap['ids_served'] / load_s, 2),
      'latency_p50_ms': round(snap['latency_p50_ms'], 3),
      'latency_p99_ms': round(snap['latency_p99_ms'], 3),
      'batch_fill_ratio': round(snap['batch_fill_ratio'], 4),
      'cache_hit_rate': round(snap['cache_hit_rate'], 4),
      'timeouts': snap['timeouts'],
      'rejected': snap['rejected'],
      'warmup_seconds': round(warmup_s, 2),
      'steady_state_recompiles': sum(
          compile_end['forward_traces'].values()) - sum(
          compile_after_warmup['forward_traces'].values()),
      'forward_calls': compile_end['forward_calls'],
      'errors': errors,
      'config': {
          'num_nodes': args.num_nodes, 'fanout': fanout,
          'buckets': buckets, 'max_request': args.max_request,
          'max_wait_ms': args.max_wait_ms,
          'cache_capacity': args.cache_capacity,
          'hidden': args.hidden,
      },
  }
  print(json.dumps(report))
  if errors:
    sys.exit(1)


if __name__ == '__main__':
  main()
