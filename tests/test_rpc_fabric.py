"""Any-to-any rpc fabric (reference rpc.py:240-529 surface): init_rpc
rendezvous, cross-rank requests, role-scoped collectives, partition
router. Pure sockets — no jax backend involved."""
import multiprocessing as mp
import socket

import numpy as np
import pytest


def _free_port() -> int:
  s = socket.socket()
  s.bind(('127.0.0.1', 0))
  port = s.getsockname()[1]
  s.close()
  return port


def _fabric_worker(rank: int, world: int, port: int, q) -> None:
  try:
    from glt_tpu.distributed import (
        RpcCalleeBase, RpcDataPartitionRouter, all_gather, barrier,
        global_all_gather, init_rpc, rpc_is_initialized, rpc_register,
        rpc_request, rpc_request_async, rpc_sync_data_partitions,
        shutdown_rpc,
    )
    assert not rpc_is_initialized()
    init_rpc('127.0.0.1', port, rank=rank, world_size=world)
    assert rpc_is_initialized()

    class Doubler(RpcCalleeBase):
      def call(self, x):
        return (rank, np.asarray(x) * 2)

    rpc_register('double', Doubler())
    barrier()  # all callees registered before anyone requests

    # every rank calls every OTHER rank (and itself through the socket)
    for dst in range(world):
      got_rank, doubled = rpc_request(dst, 'double', np.arange(3))
      assert got_rank == dst
      np.testing.assert_array_equal(doubled, np.arange(3) * 2)
    fut = rpc_request_async((rank + 1) % world, 'double', 7)
    assert fut.result(timeout=60)[1] == 14

    gathered = all_gather(f'v{rank}')
    assert gathered == {r: f'v{r}' for r in range(world)}
    gathered2 = global_all_gather(rank * 10)
    assert gathered2 == {r: r * 10 for r in range(world)}

    # partition->workers map + router: rank r serves partitions {r, r+1}
    p2w = rpc_sync_data_partitions([rank, (rank + 1) % world])
    assert sorted(p2w) == list(range(world))
    for p, ws in p2w.items():
      assert sorted(ws) == sorted({p, (p - 1) % world})
    router = RpcDataPartitionRouter(p2w)
    picks = {router.get_to_worker(0) for _ in range(4)}
    assert picks == set(p2w[0])  # round-robin covers every server

    shutdown_rpc()
    assert not rpc_is_initialized()
    q.put((rank, 'ok'))
  except BaseException as e:  # surface the failure to the parent
    q.put((rank, f'FAIL: {type(e).__name__}: {e}'))


def test_rpc_fabric_three_ranks():
  world = 3
  port = _free_port()
  ctx = mp.get_context('spawn')
  q = ctx.Queue()
  procs = [ctx.Process(target=_fabric_worker, args=(r, world, port, q))
           for r in range(world)]
  for p in procs:
    p.start()
  # generous: each spawned worker pays the full package import, and the
  # suite often runs alongside long background benchmarks on one core
  results = [q.get(timeout=600) for _ in range(world)]
  for p in procs:
    p.join(timeout=120)
  assert all(msg == 'ok' for _, msg in results), results


def test_rpc_fabric_requires_identity_without_context(monkeypatch):
  from glt_tpu.distributed import dist_context, init_rpc
  # an earlier file on this xdist worker may have left its context set
  # (init_server / init_client without a shutdown)
  monkeypatch.setattr(dist_context, '_context', None)
  with pytest.raises(ValueError, match='rank/world_size'):
    init_rpc('127.0.0.1', _free_port())


def test_rpc_fabric_master_port_zero_rejected():
  from glt_tpu.distributed import init_rpc
  with pytest.raises(ValueError, match='concrete pre-agreed port'):
    init_rpc('127.0.0.1', 0, rank=0, world_size=1)


def test_rpc_server_waits_for_late_registration():
  # a peer can discover the server before user code registers; the
  # lookup waits instead of failing (the KeyError('push_edges') race)
  import threading
  import time
  from glt_tpu.distributed import RpcClient, RpcServer
  server = RpcServer()
  try:
    client = RpcClient(server.host, server.port)
    threading.Timer(0.5, lambda: server.register(
        'late', lambda x: x + 1)).start()
    t0 = time.monotonic()
    assert client.request('late', 41) == 42
    assert time.monotonic() - t0 < 30
    client.close()
  finally:
    server.stop()


def test_concurrent_event_loop():
  import threading
  import time
  from glt_tpu.distributed import ConcurrentEventLoop
  loop = ConcurrentEventLoop(concurrency=2)
  active = [0]
  peak = [0]
  lock = threading.Lock()

  def task(i):
    with lock:
      active[0] += 1
      peak[0] = max(peak[0], active[0])
    time.sleep(0.05)
    with lock:
      active[0] -= 1
    return i * 2

  got = []
  for i in range(6):
    loop.add_task(task, i, callback=got.append)
  loop.wait_all()
  assert sorted(got) == [0, 2, 4, 6, 8, 10]
  assert peak[0] <= 2  # bounded in-flight window
  assert loop.run_task(task, 21) == 42
  # failures surface at wait_all
  loop.add_task(lambda: (_ for _ in ()).throw(RuntimeError('boom')))
  with pytest.raises(RuntimeError, match='boom'):
    loop.wait_all()
  loop.shutdown()


def test_concurrent_event_loop_error_semantics():
  """ADVICE r4: nested submission fails loudly instead of deadlocking;
  callback exceptions land in the future (not the executor logger) and
  run only on success; run_task failures are consumed (wait_all must
  not re-raise them)."""
  from glt_tpu.distributed import ConcurrentEventLoop
  loop = ConcurrentEventLoop(concurrency=1)

  # nested add_task to the SAME loop -> loud error captured in future
  def nested():
    loop.add_task(lambda: None)
  with pytest.raises(RuntimeError, match='nested add_task'):
    loop.run_task(nested)

  # ...but a SIBLING loop is a legal nested stage
  sibling = ConcurrentEventLoop(concurrency=1)
  assert loop.run_task(lambda: sibling.run_task(lambda: 7)) == 7
  sibling.shutdown()

  # callback errors surface through the future
  def bad_cb(_):
    raise ValueError('callback blew up')
  fut = loop.add_task(lambda: 1, callback=bad_cb)
  with pytest.raises(ValueError, match='callback blew up'):
    fut.result()
  loop._pending.clear()  # consumed above

  # a failing task never invokes its callback
  ran = []
  fut = loop.add_task(
      lambda: (_ for _ in ()).throw(RuntimeError('task failed')),
      callback=ran.append)
  with pytest.raises(RuntimeError, match='task failed'):
    fut.result()
  assert ran == []
  loop._pending.clear()

  # run_task consumes its own failure: wait_all stays clean
  with pytest.raises(RuntimeError, match='once only'):
    loop.run_task(lambda: (_ for _ in ()).throw(
        RuntimeError('once only')))
  loop.wait_all()  # must NOT re-raise
  loop.shutdown()


def _role_worker(rank: int, world: int, port: int, q) -> None:
  try:
    from glt_tpu.distributed import (
        all_gather, barrier, init_rpc, init_worker_group, shutdown_rpc,
    )
    # role-scoped collectives resolve identity + world from the
    # DistContext (reference role-group all_gather, rpc.py:105-211)
    init_worker_group(world_size=world, rank=rank)
    init_rpc('127.0.0.1', port)  # rank/world from the context
    barrier()
    got = all_gather(rank + 100)
    assert got == {r: r + 100 for r in range(world)}, got
    shutdown_rpc()
    q.put((rank, 'ok'))
  except BaseException as e:
    q.put((rank, f'FAIL: {type(e).__name__}: {e}'))


def test_rpc_fabric_role_scoped_collectives():
  world = 2
  port = _free_port()
  ctx = mp.get_context('spawn')
  q = ctx.Queue()
  procs = [ctx.Process(target=_role_worker, args=(r, world, port, q))
           for r in range(world)]
  for p in procs:
    p.start()
  results = [q.get(timeout=600) for _ in range(world)]
  for p in procs:
    p.join(timeout=120)
  assert all(msg == 'ok' for _, msg in results), results


def _slow_reply_worker(rank: int, world: int, port: int, q) -> None:
  try:
    import time
    from glt_tpu.distributed import barrier, init_rpc, rpc, shutdown_rpc
    init_rpc('127.0.0.1', port, rank=rank, world_size=world)
    if rank == 0:
      # the master's serve threads hold every PEER's barrier reply for
      # half a second (rank 0's own goes out at once): what a loaded
      # machine does to them now and then
      send = rpc._send_msg
      # read once: the hook also sends the replies of the shutdown, after
      # the fabric's context is gone
      own = rpc._fabric['ctx'].master._sock.getsockname()

      def held(conn, msg):
        if msg == ('ok', True) and conn.getpeername() != own:
          time.sleep(0.5)
        return send(conn, msg)

      rpc._send_msg = held
    barrier()
    shutdown_rpc()
    q.put((rank, 'ok'))
  except BaseException as e:
    q.put((rank, f'FAIL: {type(e).__name__}: {e}'))


def test_rpc_shutdown_waits_for_the_peers_barrier_replies():
  """The master must not stop its server while a peer's reply to the
  final barrier is still in a serve thread's hands: the peer would read
  'peer closed' (the flake of the two tests above under six workers)."""
  world = 3
  port = _free_port()
  ctx = mp.get_context('spawn')
  q = ctx.Queue()
  procs = [ctx.Process(target=_slow_reply_worker,
                       args=(r, world, port, q)) for r in range(world)]
  for p in procs:
    p.start()
  results = [q.get(timeout=600) for _ in range(world)]
  for p in procs:
    p.join(timeout=120)
  assert all(msg == 'ok' for _, msg in results), results
