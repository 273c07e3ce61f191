"""Multiprocess RecordIO image pipeline (parent side).

The reference's ImageRecordIter throughput comes from a C++ pipeline:
OMP-parallel RecordIO parse + OpenCV decode + augment feeding batch
buffers, with a prefetcher thread on top (reference:
src/io/iter_image_recordio_2.cc:28-595, iter_prefetcher.h:129). The
Python-thread pool in image.py caps out around a few hundred img/s/core
because augmentation fights the GIL.

This module is the scalable path: N worker *processes* (see
_decode_worker.py — self-contained, never imports JAX), each owning its
own file handle on the ``.rec`` pack. The parent scans the pack once
for record frame offsets (header-only seek walk, no decode), then per
batch sends each worker a shard of offsets; workers decode+augment into
shared-memory staging slots and the parent assembles a batch with one
memcpy per shard. Two slots per worker double-buffer, so batch k+1 is
decoding across all cores while the training step consumes batch k.
Decode throughput scales with cores — the design target is the
reference bar of >=1000 img/s/host (not measured on the chip's host;
no benchmark cell reads a ``.rec`` pack yet).

``ImageRecordIter`` (image.py) routes here automatically when its
augmentation is the param-driven CreateAugmenter set; closure-based
custom aug lists keep the thread-pool path. ``MXNET_DECODE_WORKERS``
overrides the worker count (0 disables the multiprocess path).
"""
from __future__ import annotations

import collections
import json
import os
import struct
import subprocess
import sys
import tempfile
from multiprocessing import shared_memory

import numpy as np

from .base import MXNetError
from .io import DataBatch, DataDesc, DataIter
from .ndarray import array

__all__ = ["scan_record_offsets", "MPImageRecordIter"]

_K_MAGIC = 0xced7230a


def scan_record_offsets(rec_path):
    """Walk the pack's frame headers and return every record's
    frame-start offset (no payload reads — this is an O(n_records) seek
    loop, the indexless analog of the reference's .idx sidecar)."""
    offsets = []
    size = os.path.getsize(rec_path)
    with open(rec_path, "rb") as f:
        pos = 0
        while pos + 8 <= size:
            f.seek(pos)
            magic, lrec = struct.unpack("<II", f.read(8))
            if magic != _K_MAGIC:
                raise MXNetError(f"bad RecordIO magic at {pos}")
            length = lrec & ((1 << 29) - 1)
            offsets.append(pos)
            pos += 8 + length + ((4 - length % 4) % 4)
    return offsets


def _load_idx_offsets(idx_path):
    offsets = []
    with open(idx_path) as f:
        for line in f:
            parts = line.strip().split("\t")
            if len(parts) == 2:
                offsets.append(int(parts[1]))
    return offsets


class MPImageRecordIter(DataIter):
    """RecordIO iterator with multiprocess decode into shared memory."""

    def __init__(self, path_imgrec, data_shape, batch_size,
                 path_imgidx=None, label_width=1, shuffle=False,
                 part_index=0, num_parts=1, aug_params=None,
                 num_workers=None, seed=0, data_name="data",
                 label_name="softmax_label"):
        super().__init__(batch_size)
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self.data_name = data_name
        self.label_name = label_name
        self._aug = dict(aug_params or {})
        self._seed = seed
        self._epoch = 0

        if path_imgidx and os.path.exists(path_imgidx):
            offsets = _load_idx_offsets(path_imgidx)
        else:
            offsets = scan_record_offsets(path_imgrec)
        if num_parts > 1:
            n = len(offsets) // num_parts
            offsets = offsets[part_index * n:(part_index + 1) * n]
        if not offsets:
            raise MXNetError(f"no records in {path_imgrec}")
        self._offsets = np.asarray(offsets, dtype=np.int64)
        self._shuffle = shuffle

        if num_workers is None:
            num_workers = int(os.environ.get(
                "MXNET_DECODE_WORKERS", min(os.cpu_count() or 1, 8)))
        self._W = max(1, min(num_workers, batch_size))
        self._Q = 2                       # slots per worker (double buffer)
        self._slot_imgs = -(-batch_size // self._W)

        c, h, w = self.data_shape
        self._img_floats = c * h * w
        self._slot_floats = self._slot_imgs * (self._img_floats
                                               + label_width)
        n_slots = self._W * self._Q
        self._shm = shared_memory.SharedMemory(
            create=True, size=n_slots * self._slot_floats * 4)
        self._buf = np.ndarray((n_slots * self._slot_floats,),
                               dtype=np.float32, buffer=self._shm.buf)

        worker_py = os.path.join(os.path.dirname(
            os.path.abspath(__file__)), "_decode_worker.py")
        self._procs, self._cfg_files = [], []
        for wi in range(self._W):
            cfg = {"rec_path": path_imgrec, "shm_name": self._shm.name,
                   "n_slots": n_slots, "slot_imgs": self._slot_imgs,
                   "data_shape": list(self.data_shape),
                   "label_width": label_width, "aug": self._aug,
                   "seed": seed * 1000003 + wi}
            cf = tempfile.NamedTemporaryFile(
                "w", suffix=".json", delete=False)
            json.dump(cfg, cf)
            cf.close()
            self._cfg_files.append(cf.name)
            # keep stderr in a file so a dead worker is diagnosable
            ef = tempfile.NamedTemporaryFile(
                "w", suffix=".log", delete=False)
            self._cfg_files.append(ef.name)
            self._err_files = getattr(self, "_err_files", [])
            self._err_files.append(ef.name)
            self._procs.append(subprocess.Popen(
                [sys.executable, worker_py, cf.name],
                stdin=subprocess.PIPE, stdout=subprocess.PIPE,
                stderr=ef, text=True))
            ef.close()
        self._inflight = []               # [(pad, [(worker, slot, n)])]
        # per-worker FIFO of slots awaiting a reply: every reply is
        # matched against the slot it was dispatched for, and error/reset
        # paths drain each stream exactly — otherwise a partially-read
        # batch would desynchronize replies from slots and the parent
        # could copy a slot the worker hasn't confirmed writing
        self._pending = [collections.deque() for _ in range(self._W)]
        self._cursor = 0
        self._order = None
        self.reset()

    # ------------------------------------------------------------- protocol
    def _queue_batch(self, outbox):
        """Stage one batch's offset shards as per-worker orders."""
        start = self._cursor
        idxs = self._order[start:start + self.batch_size]
        if len(idxs) == 0:
            return False
        self._cursor += len(idxs)
        pad = self.batch_size - len(idxs)
        offs = self._offsets[idxs]
        shards = []
        base_slot = (self._seq % self._Q)
        self._seq += 1
        per = self._slot_imgs
        for wi in range(self._W):
            shard = offs[wi * per:(wi + 1) * per]
            if len(shard) == 0:
                break
            slot = wi * self._Q + base_slot
            outbox[wi].append({"slot": slot,
                               "items": [int(o) for o in shard]})
            self._pending[wi].append(slot)
            shards.append((wi, slot, len(shard)))
        self._inflight.append((pad, shards))
        return True

    def _dispatch_batches(self, n):
        """Dispatch up to n batches' decode work, chunked into at most
        ONE stdin write per worker — the json-encode + pipe-syscall cost
        is paid per chunk, not per batch (the priming path covers all Q
        double-buffer slots in a single message per worker)."""
        outbox = [[] for _ in range(self._W)]
        count = 0
        for _ in range(n):
            if not self._queue_batch(outbox):
                break
            count += 1
        for wi, orders in enumerate(outbox):
            if not orders:
                continue
            msg = orders[0] if len(orders) == 1 else {"orders": orders}
            try:
                self._procs[wi].stdin.write(json.dumps(msg) + "\n")
                self._procs[wi].stdin.flush()
            except (BrokenPipeError, OSError):
                raise MXNetError(
                    f"decode worker {wi} died "
                    f"(rc={self._procs[wi].poll()}): "
                    f"{self._worker_stderr(wi)}")
        return count

    def _collect_batch(self):
        if not self._inflight:
            raise StopIteration
        pad, shards = self._inflight.pop(0)
        c, h, w = self.data_shape
        data = np.zeros((self.batch_size, c, h, w), dtype=np.float32)
        labels = np.zeros((self.batch_size, self.label_width),
                          dtype=np.float32)
        row = 0
        for wi, slot, n in shards:
            rep = self._read_reply(wi)
            if rep.get("slot") != slot:
                raise MXNetError(
                    f"decode worker {wi} reply for slot "
                    f"{rep.get('slot')} but slot {slot} expected — "
                    "parent/worker streams desynchronized")
            if "error" in rep:
                raise MXNetError(f"decode worker {wi}: {rep['error']}")
            base = slot * self._slot_floats
            imgs = self._buf[base:base + self._slot_imgs
                             * self._img_floats].reshape(
                self._slot_imgs, c, h, w)
            labs = self._buf[base + self._slot_imgs * self._img_floats:
                             base + self._slot_floats].reshape(
                self._slot_imgs, self.label_width)
            data[row:row + n] = imgs[:n]
            labels[row:row + n] = labs[:n]
            row += n
        return data, labels, pad

    def _read_reply(self, wi):
        """Read one reply line from worker wi and retire its oldest
        pending slot; the caller validates the echoed slot id."""
        line = self._procs[wi].stdout.readline()
        if not line:
            raise MXNetError(
                f"decode worker {wi} died (rc="
                f"{self._procs[wi].poll()}): "
                f"{self._worker_stderr(wi)}")
        if self._pending[wi]:
            self._pending[wi].popleft()
        return json.loads(line)

    def _worker_stderr(self, wi, tail=500):
        try:
            with open(self._err_files[wi]) as f:
                txt = f.read()
            return txt[-tail:] if txt else "(no stderr)"
        except Exception:
            return "(stderr unavailable)"

    # ------------------------------------------------------------ DataIter
    @property
    def provide_data(self):
        return [DataDesc(self.data_name,
                         (self.batch_size,) + self.data_shape)]

    @property
    def provide_label(self):
        shape = (self.batch_size,) if self.label_width == 1 else \
            (self.batch_size, self.label_width)
        return [DataDesc(self.label_name, shape)]

    def reset(self):
        # drain every outstanding reply (not just whole in-flight
        # batches: an error may have left a batch partially read) so
        # slots are quiescent before reordering
        for wi in range(self._W):
            while self._pending[wi]:
                self._read_reply(wi)
        self._inflight.clear()
        n = len(self._offsets)
        if self._shuffle:
            rng = np.random.default_rng(self._seed + self._epoch)
            self._order = rng.permutation(n)
        else:
            self._order = np.arange(n)
        self._epoch += 1
        self._cursor = 0
        self._seq = 0
        self._dispatch_batches(self._Q)   # prime: one chunked message
                                          # per worker covers all slots

    def next(self):
        data, labels, pad = self._collect_batch()
        self._dispatch_batches(1)
        lab = labels[:, 0] if self.label_width == 1 else labels
        return DataBatch([array(data)], [array(lab)], pad=pad)

    def close(self):
        for p in self._procs:
            try:
                p.stdin.write('{"cmd": "quit"}\n')
                p.stdin.flush()
                p.stdin.close()
            except Exception:
                pass
            try:
                p.wait(timeout=5)
            except Exception:
                p.kill()
        self._procs = []
        try:
            self._buf = None
            self._shm.close()
            self._shm.unlink()
        except Exception:
            pass
        for cf in self._cfg_files:
            try:
                os.unlink(cf)
            except (OSError, AttributeError):
                pass              # AttributeError: interpreter shutdown
        self._cfg_files = []

    def __del__(self):
        if getattr(self, "_procs", None):
            self.close()
