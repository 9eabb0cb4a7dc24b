"""ROADMAP S4(a): the link, apart from the program (a throwaway probe).

    chiprun --timeout 900 -- python3 tests/scripts/link_on_chip.py

Times, with ``block_until_ready`` between, the three parts of one dispatch
of the batch scheduler (host -> device copy of the operand, the program on
a resident operand, device -> host copy of the result), each alone; then
the dispatcher's serial cycle as served (program called on the host
array, then ``device_get``); then the same jobs with job N+1 launched
before job N is collected, three ways: on one thread, on one thread with
``copy_to_host_async`` at launch, and with a second thread that collects.
Shapes: a seal's job under RS(10,4) and RS(6,3), and one full dispatch of
the chunk cell's rebuilds (B = 4 at the 1 MiB rung).  Prints one JSON
line a shape, medians in ms and GB/s; ``chiprun_out/link/link.json``
keeps them.  (On the CPU backend: ``JAX_PLATFORMS=cpu ... --reps 3``
rehearses the control flow and says nothing about a link.)
"""

from __future__ import annotations

import argparse
import json
import os
import queue
import statistics
import sys
import threading
import time

import numpy as np

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)))))


def med(xs):
    return round(statistics.median(xs) * 1e3, 4)


def timed(fn, reps):
    out = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        out.append(time.perf_counter() - t0)
    return out


def probe(name, mesh, fn, operands, reps, jobs):
    """``operands``: host arrays of one job (the first is the data)."""
    import jax
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    sh = [mesh_mod.batch_spec(mesh, rank=o.ndim) for o in operands]
    # distinct host buffers a job, as served (a seal's batches are)
    hosts = [[np.ascontiguousarray(o + np.uint32(i)) for o in operands]
             for i in range(4)]
    in_bytes = sum(o.nbytes for o in operands)

    def put(i=0):
        return [jax.device_put(o, s) for o, s in zip(hosts[i % 4], sh)]

    dev = put()
    jax.block_until_ready(dev)
    out = fn(*dev)
    out.block_until_ready()
    out_bytes = out.nbytes
    for _ in range(3):                       # warm every path once more
        np.asarray(jax.device_get(fn(*hosts[0])))

    t_put = timed(lambda: jax.block_until_ready(put()), reps)
    t_prog = timed(lambda: fn(*dev).block_until_ready(), reps)

    def get_alone():
        o = fn(*dev)
        o.block_until_ready()
        t0 = time.perf_counter()
        np.asarray(jax.device_get(o))
        return time.perf_counter() - t0
    t_get = [get_alone() for _ in range(reps)]

    def launch(i):
        return fn(*hosts[i % 4])

    def collect(o):
        return np.asarray(jax.device_get(o))

    # launch alone (returns before the device is done?) and the rest
    t_launch, t_fetch = [], []
    for i in range(reps):
        t0 = time.perf_counter()
        o = launch(i)
        t1 = time.perf_counter()
        collect(o)
        t_launch.append(t1 - t0)
        t_fetch.append(time.perf_counter() - t1)

    def serial():
        for i in range(jobs):
            collect(launch(i))

    def two_one_thread(async_copy):
        prev = None
        for i in range(jobs):
            o = launch(i)
            if async_copy:
                o.copy_to_host_async()
            if prev is not None:
                collect(prev)
            prev = o
        collect(prev)

    def two_threads():
        q: "queue.Queue" = queue.Queue()
        slots = threading.Semaphore(2)

        def collector():
            while True:
                o = q.get()
                if o is None:
                    return
                collect(o)
                slots.release()
        th = threading.Thread(target=collector)
        th.start()
        for i in range(jobs):
            slots.acquire()
            q.put(launch(i))
        q.put(None)
        th.join()

    def per_job(run):
        xs = []
        for _ in range(max(3, reps // 8)):
            t0 = time.perf_counter()
            run()
            xs.append((time.perf_counter() - t0) / jobs)
        return xs

    # a copy in beside a program + copy out: how much of it hides
    def in_under_out():
        o = fn(*dev)
        t0 = time.perf_counter()
        nxt = put(1)
        got = collect(o)
        jax.block_until_ready(nxt)
        del got
        return time.perf_counter() - t0
    t_both = [in_under_out() for _ in range(reps)]

    def out_alone():
        o = fn(*dev)
        t0 = time.perf_counter()
        collect(o)
        return time.perf_counter() - t0
    t_out = [out_alone() for _ in range(reps)]

    line = {
        "shape": name, "in_bytes": in_bytes, "out_bytes": out_bytes,
        "put_ms": med(t_put), "program_ms": med(t_prog),
        "get_ms": med(t_get),
        "in_gbps": round(in_bytes / statistics.median(t_put) / 1e9, 3),
        "out_gbps": round(out_bytes / statistics.median(t_get) / 1e9, 3),
        "launch_ms": med(t_launch), "fetch_ms": med(t_fetch),
        "serial_job_ms": med(per_job(serial)),
        "two_one_thread_job_ms": med(per_job(
            lambda: two_one_thread(False))),
        "two_one_thread_async_copy_job_ms": med(per_job(
            lambda: two_one_thread(True))),
        "two_threads_job_ms": med(per_job(two_threads)),
        "program_and_out_ms": med(t_out),
        "in_beside_program_and_out_ms": med(t_both),
    }
    # of the copy in's time, the share that did not add to the other's
    hidden = (statistics.median(t_put) + statistics.median(t_out)
              - statistics.median(t_both)) / statistics.median(t_put)
    line["copy_in_hidden_share"] = round(hidden, 3)
    return line


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--reps", type=int, default=40)
    ap.add_argument("--jobs", type=int, default=52)
    ap.add_argument("--words", type=int, default=262144)
    args = ap.parse_args()

    import jax
    from seaweedfs_tpu.models.coder import RSScheme
    from seaweedfs_tpu.ops import rs_mesh
    from seaweedfs_tpu.parallel import mesh as mesh_mod

    mesh_mod.ensure_compile_cache()
    mesh = mesh_mod.batch_mesh(1)
    rng = np.random.default_rng(36)
    nw = args.words
    lines = [{"device": mesh_mod.device_report(list(mesh.devices.flat)),
              "jax": jax.__version__, "reps": args.reps, "jobs": args.jobs}]
    for name, scheme, kind, b in (
            ("encode rs-10-4 (1,10,nw)->(1,4,nw)", RSScheme(10, 4), "e", 1),
            ("encode rs-6-3 (1,6,nw)->(1,3,nw)", RSScheme(6, 3), "e", 1),
            ("apply rs-10-4 (4,10,nw)->(4,4,nw)", RSScheme(10, 4), "a", 4)):
        k, m = scheme.data_shards, scheme.parity_shards
        words = rng.integers(0, 2 ** 32, (b, k, nw), dtype=np.uint32)
        if kind == "e":
            fn = rs_mesh.batch_encode_fn(scheme, mesh)
            operands = [words]
        else:
            fn = rs_mesh.batch_apply_fn(scheme, mesh)
            operands = [words, rng.integers(0, 256, (b, m, k),
                                            dtype=np.uint32)]
        line = probe(name, mesh, fn, operands, args.reps, args.jobs)
        print(json.dumps(line), flush=True)
        lines.append(line)
    os.makedirs("chiprun_out/link", exist_ok=True)
    with open("chiprun_out/link/link.json", "w") as f:
        json.dump(lines, f, indent=1)


if __name__ == "__main__":
    main()
