"""``run.py``'s command rehearsed in a process of its own (``--rehearse``,
on the CPU).  A traced run clears ``.bench_trace/<cell>`` under its root
and writes its trace there, so two traced rehearsals of one cell in one
tree, from two test workers at once, read each other's files (a reader
then finds nothing and its metric is left out): a traced rehearsal holds
a lock of its cell's while it runs."""
import fcntl
import os
import subprocess
import sys


def run(root: str, cell: str, seed: int, trace: int):
    command = [sys.executable, os.path.join(root, "benchmarks", "run.py"),
               "--workload", cell, "--seed", str(seed), "--seconds", "1",
               "--trace", str(trace), "--rehearse"]
    if not trace:
        return subprocess.run(command, capture_output=True, text=True,
                              timeout=600, cwd=root)
    lock = os.path.join(root, ".bench_trace", cell + ".lock")
    os.makedirs(os.path.dirname(lock), exist_ok=True)
    with open(lock, "w") as held:
        fcntl.flock(held, fcntl.LOCK_EX)
        return subprocess.run(command, capture_output=True, text=True,
                              timeout=600, cwd=root)
