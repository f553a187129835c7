"""The window's YCSB batches, made in a child process ahead of the timed
loop and handed over through a ring of slots in shared memory.

Batch ``i`` is ``ycsb.Stream.batch(i)`` of the run's seed, its record
values (``ycsb.record_values``) and its ops as the program's op codes, made
in order.  The child is a fresh interpreter (``python -m bench.kv_ring``,
never a fork) that imports numpy and the generator alone, never JAX, so it
can take no hold of the chip and shares no interpreter lock with the loop
that times the program.

The ring is an anonymous memory file both processes map; two pipes count
its slots, one byte a slot: the parent writes to ``free`` when it is done
with a slot, the child to ``filled`` when it has made a batch.  The parent
``take``s batches in order and ``release``s each once it is done with the
slot's arrays; the child refills a slot only after its release.  ``close``
closes ``free``; the child reads its end, exits, and is waited for.  Should
the parent die instead, the child reads the same end, and the kernel frees
the memory with the last process that maps it: nothing is left behind.
"""

from __future__ import annotations

import json
import mmap
import os
import subprocess
import sys
import time
from pathlib import Path

import numpy as np

from bench.generators import ycsb

ROOT = Path(__file__).resolve().parent.parent


def make_batch(stream: ycsb.Stream, i: int, op_codes, out=None):
    """Batch ``i`` of ``stream`` as (keys int32 (B,), vals int32 (B, 2),
    program op codes int32 (B,)); ``op_codes`` are the program's codes of
    a read and an update.  Written into ``out`` where it is given."""
    keys, ops = stream.batch(i)
    vals = ycsb.record_values(keys)
    prog = np.asarray(op_codes, np.int32)[ops.astype(np.int64)]
    if out is None:
        return keys, vals, prog
    for dst, src in zip(out, (keys, vals, prog)):
        dst[...] = src
    return out


def _size(slots: int, batch: int) -> int:
    return 8 + 8 * slots + slots * batch * 4 * 4


def _views(buf, slots: int, batch: int):
    """(made (1,), index (slots,), keys, vals, ops) over ``buf``."""
    made = np.ndarray((1,), np.int64, buf, 0)
    index = np.ndarray((slots,), np.int64, buf, 8)
    off = 8 + 8 * slots
    keys = np.ndarray((slots, batch), np.int32, buf, off)
    off += keys.nbytes
    vals = np.ndarray((slots, batch, 2), np.int32, buf, off)
    off += vals.nbytes
    ops = np.ndarray((slots, batch), np.int32, buf, off)
    return made, index, keys, vals, ops


def _produce(spec: dict, mem: int, free: int, filled: int):
    """The child: fills slot ``i % slots`` with batch ``i`` once it is
    free, for i = 0, 1, ... until ``free`` is closed."""
    if "jax" in sys.modules:
        raise RuntimeError("the batch generator must not import JAX")
    slots, batch = spec["slots"], spec["batch"]
    with mmap.mmap(mem, _size(slots, batch)) as buf:
        made, index, keys, vals, ops = _views(buf, slots, batch)
        stream = ycsb.Stream(spec["traffic"], spec["key_space"], batch,
                             spec["seed"])
        i = 0
        while os.read(free, 1):
            s = i % slots
            make_batch(stream, i, spec["op_codes"], (keys[s], vals[s], ops[s]))
            index[s] = i
            i += 1
            made[0] = i
            os.write(filled, b"\1")
        del made, index, keys, vals, ops


class BatchRing:
    """Parent side of the ring: ``take(i)`` waits for batch ``i`` and gives
    views of its slot, valid until ``release()``."""

    def __init__(self, traffic: dict, key_space: int, batch: int, seed: int,
                 op_codes, slots: int = 16):
        self.slots, self.batch = slots, batch
        self.fd = os.memfd_create("kv-batch-ring")
        os.ftruncate(self.fd, _size(slots, batch))
        self.buf = mmap.mmap(self.fd, _size(slots, batch))
        self.made, self.index, self.keys, self.vals, self.ops = _views(
            self.buf, slots, batch)
        free_r, self.free = os.pipe()
        self.filled, filled_w = os.pipe()
        spec = {"slots": slots, "batch": batch, "traffic": traffic,
                "key_space": int(key_space), "seed": int(seed),
                "op_codes": [int(c) for c in op_codes]}
        self.proc = None
        try:
            self.proc = subprocess.Popen(
                [sys.executable, "-m", "bench.kv_ring", json.dumps(spec),
                 str(self.fd), str(free_r), str(filled_w)],
                cwd=ROOT, pass_fds=(self.fd, free_r, filled_w))
        except BaseException:
            self.close()
            raise
        finally:
            os.close(free_r)
            os.close(filled_w)
        os.write(self.free, b"\1" * slots)

    def wait_full(self, timeout: float = 120.0):
        """Blocks until every slot holds a batch (before the window)."""
        t = time.perf_counter() + timeout
        while self.made[0] < self.slots:
            if self.proc.poll() is not None:
                raise RuntimeError(f"the batch generator exited with code "
                                   f"{self.proc.returncode}")
            if time.perf_counter() > t:
                raise TimeoutError("the batch generator did not fill the ring")
            time.sleep(0.001)

    def take(self, i: int):
        """(keys, vals, ops) of batch ``i``; batches are taken in order."""
        if not os.read(self.filled, 1):
            raise RuntimeError(f"the batch generator exited with code "
                               f"{self.proc.wait()}")
        s = i % self.slots
        if self.index[s] != i:
            raise RuntimeError(f"ring slot {s} holds batch {self.index[s]}, "
                               f"not {i}")
        return self.keys[s], self.vals[s], self.ops[s]

    def release(self):
        """Frees the oldest slot taken."""
        os.write(self.free, b"\1")

    def close(self):
        """Stops the child, waits for it, and frees the ring."""
        os.close(self.free)
        if self.proc is not None:
            try:
                self.proc.wait(timeout=10)
            except subprocess.TimeoutExpired:
                self.proc.kill()
                self.proc.wait()
        os.close(self.filled)
        del self.made, self.index, self.keys, self.vals, self.ops
        try:
            self.buf.close()
        except BufferError:
            # an array still maps a slot (a device array on the CPU may
            # alias its input); the mapping goes with that array
            pass
        os.close(self.fd)

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.close()


if __name__ == "__main__":
    _produce(json.loads(sys.argv[1]), *map(int, sys.argv[2:5]))
