"""Ray-Client-equivalent tests: a separate server process owns the cluster;
this test process drives it purely over the client protocol (it never joins
the cluster). ≈ the reference's `python/ray/util/client/` test surface.
"""

import os
import subprocess
import sys
import time

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

SERVER_SCRIPT = """
import asyncio, sys
sys.path.insert(0, %r)
from ray_tpu.util.client.server import ClientServer

async def main():
    srv = ClientServer(None, host="127.0.0.1", port=0,
                       init_kwargs={"num_cpus": 8,
                                    "object_store_memory": 128 * 1024 * 1024})
    addr = await srv.start()
    print("READY %%d" %% addr[1], flush=True)
    await asyncio.Event().wait()

asyncio.run(main())
""" % REPO


@pytest.fixture(scope="module")
def client_cluster():
    env = dict(os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.Popen([sys.executable, "-c", SERVER_SCRIPT],
                            stdout=subprocess.PIPE,
                            stderr=subprocess.STDOUT, text=True, env=env)
    port = None
    deadline = time.time() + 60
    while time.time() < deadline:
        line = proc.stdout.readline()
        if line.startswith("READY"):
            port = int(line.split()[1])
            break
        if proc.poll() is not None:
            raise RuntimeError(f"client server died: {proc.stdout.read()}")
    assert port, "client server never came up"

    import ray_tpu

    info = ray_tpu.init(address=f"client://127.0.0.1:{port}")
    assert info.get("client")
    yield port
    ray_tpu.shutdown()
    proc.terminate()
    proc.wait(timeout=10)


def test_put_get_roundtrip(client_cluster):
    import numpy as np

    import ray_tpu

    ref = ray_tpu.put({"a": np.arange(1000), "b": "hello"})
    out = ray_tpu.get(ref)
    assert out["b"] == "hello"
    np.testing.assert_array_equal(out["a"], np.arange(1000))


def test_remote_task_and_nested_refs(client_cluster):
    import ray_tpu

    @ray_tpu.remote
    def add(x, y):
        return x + y

    a = ray_tpu.put(10)
    r1 = add.remote(a, 5)          # client ref as an arg
    r2 = add.remote(r1, [1, 2][0])  # chained ref
    assert ray_tpu.get(r2) == 16


def test_task_exception_propagates(client_cluster):
    import ray_tpu

    @ray_tpu.remote
    def boom():
        raise ValueError("kaboom")

    with pytest.raises(Exception, match="kaboom"):
        ray_tpu.get(boom.remote())


def test_wait(client_cluster):
    import ray_tpu

    @ray_tpu.remote
    def fast():
        return 1

    @ray_tpu.remote
    def slow():
        import time as t

        t.sleep(5)
        return 2

    f, s = fast.remote(), slow.remote()
    ready, not_ready = ray_tpu.wait([f, s], num_returns=1, timeout=4)
    assert ready and ray_tpu.get(ready[0]) == 1
    assert len(not_ready) == 1


def test_actor_lifecycle(client_cluster):
    import ray_tpu

    @ray_tpu.remote
    class Counter:
        def __init__(self, start):
            self.n = start

        def incr(self, k=1):
            self.n += k
            return self.n

    c = Counter.options(name="client_counter").remote(100)
    assert ray_tpu.get(c.incr.remote()) == 101
    assert ray_tpu.get(c.incr.remote(9)) == 110

    # named lookup from the client
    c2 = ray_tpu.get_actor("client_counter")
    assert ray_tpu.get(c2.incr.remote()) == 111

    # handles can ride inside task args
    @ray_tpu.remote
    def poke(counter):
        return ray_tpu.get(counter.incr.remote(1000))

    assert ray_tpu.get(poke.remote(c)) == 1111

    ray_tpu.kill(c)
    time.sleep(0.5)
    with pytest.raises(Exception):
        ray_tpu.get(c2.incr.remote(), timeout=5)


def test_cluster_queries(client_cluster):
    import ray_tpu

    ns = ray_tpu.nodes()
    assert len(ns) >= 1
    total = ray_tpu.cluster_resources()
    assert total.get("CPU", 0) >= 8


def test_ref_release_doesnt_break_session(client_cluster):
    import gc

    import ray_tpu

    refs = [ray_tpu.put(i) for i in range(20)]
    del refs
    gc.collect()
    # next call flushes the release batch; session must still work
    assert ray_tpu.get(ray_tpu.put("still alive")) == "still alive"
