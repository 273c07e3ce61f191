"""Standalone JPEG decode/augment worker process.

The multiprocess analog of the reference's OMP-parallel RecordIO parser
(reference: src/io/iter_image_recordio_2.cc:28-595 — each OMP thread
decodes+augments a chunk of records into a preallocated output block).
Here each *process* owns a file handle on the ``.rec`` pack, receives
``(slot, [frame offsets])`` work orders on stdin, and writes decoded
float32 CHW images + labels into a shared-memory staging slot — so the
parent's per-batch cost is one memcpy, and decode throughput scales
with cores instead of fighting the GIL.

This file is deliberately self-contained (numpy + cv2 + stdlib only)
and is executed BY PATH (``python .../_decode_worker.py cfg.json``),
never imported: importing ``mxnet_tpu`` would initialize JAX (and, on a
real host, grab the TPU client) in every data worker. The RecordIO
framing it reads is the byte-stable container format
(recordio.py: [magic:4][lrec:4][payload][pad4], IRHeader "IfQQ") — the
same bytes the reference's dmlc-core reader consumes.

Augmentation implements the param-driven fast path of CreateAugmenter
(resize_short -> random/center/random-sized crop -> mirror -> cast ->
mean/std normalize), matching image.py's per-augmenter semantics.
Closure-based custom aug lists fall back to the in-process thread pool.
"""
import os
import sys

# Executed BY PATH, so sys.path[0] is this package directory — scrub it
# before any further import, or stdlib modules shadowed by framework
# files resolve wrongly and kill the worker (observed: shared_memory ->
# secrets -> `import random` landing on mxnet_tpu/random.py, which then
# pulls JAX into the decode worker and dies mid-import; on Python 3.12
# json -> re -> functools -> `import operator` landing on
# mxnet_tpu/operator.py). Only os and sys, which the interpreter has
# loaded before this file runs, may be imported above this line.
_HERE = os.path.dirname(os.path.abspath(__file__))
sys.path[:] = [p for p in sys.path
               if os.path.abspath(p or os.getcwd()) != _HERE]

import json
import struct
from multiprocessing import shared_memory

import numpy as np

_K_MAGIC = 0xced7230a
_IR_FORMAT = "IfQQ"
_IR_SIZE = struct.calcsize(_IR_FORMAT)


def _decode_lrec(lrec):
    return lrec >> 29, lrec & ((1 << 29) - 1)


def _read_record(f, offset):
    """Read one record's payload given its frame-start offset."""
    f.seek(offset)
    head = f.read(8)
    magic, lrec = struct.unpack("<II", head)
    if magic != _K_MAGIC:
        raise ValueError(f"bad RecordIO magic at {offset}")
    _, length = _decode_lrec(lrec)
    return f.read(length)


def _unpack(payload):
    flag, label, _id, _id2 = struct.unpack(_IR_FORMAT, payload[:_IR_SIZE])
    body = payload[_IR_SIZE:]
    if flag > 0:
        label = np.frombuffer(body[:flag * 4], dtype=np.float32)
        body = body[flag * 4:]
    return label, body


class Augmenter:
    """Param-driven augment chain (CreateAugmenter fast path)."""

    def __init__(self, cfg, rng):
        self.resize = int(cfg.get("resize", 0))
        self.rand_crop = bool(cfg.get("rand_crop", False))
        self.rand_resize = bool(cfg.get("rand_resize", False))
        self.rand_mirror = bool(cfg.get("rand_mirror", False))
        self.min_area = float(cfg.get("min_area", 0.3))
        self.ratio = tuple(cfg.get("ratio", (3 / 4.0, 4 / 3.0)))
        self.inter = int(cfg.get("inter", 2))
        self.mean = np.asarray(cfg["mean"], np.float32) \
            if cfg.get("mean") is not None else None
        self.std = np.asarray(cfg["std"], np.float32) \
            if cfg.get("std") is not None else None
        self.rng = rng

    def _resize(self, img, w, h):
        import cv2
        return cv2.resize(img, (w, h), interpolation=self.inter)

    def _resize_short(self, img):
        # integer arithmetic matches image.py _resize_short_np exactly
        h, w = img.shape[:2]
        if h > w:
            new_w, new_h = self.resize, self.resize * h // w
        else:
            new_w, new_h = self.resize * w // h, self.resize
        return self._resize(img, new_w, new_h)

    def _crop(self, img, cw, ch):
        h, w = img.shape[:2]
        if self.rand_resize:
            area = h * w
            for _ in range(10):
                target = self.rng.uniform(self.min_area, 1.0) * area
                ar = self.rng.uniform(*self.ratio)
                nw = int(round(np.sqrt(target * ar)))
                nh = int(round(np.sqrt(target / ar)))
                if self.rng.random() < 0.5:
                    nw, nh = nh, nw
                if nw <= w and nh <= h:
                    x0 = self.rng.integers(0, w - nw + 1)
                    y0 = self.rng.integers(0, h - nh + 1)
                    return self._resize(img[y0:y0 + nh, x0:x0 + nw], cw, ch)
            # fallthrough: center crop
        if self.rand_crop and not self.rand_resize:
            x0 = self.rng.integers(0, max(w - cw, 0) + 1)
            y0 = self.rng.integers(0, max(h - ch, 0) + 1)
        else:
            x0 = max((w - cw) // 2, 0)
            y0 = max((h - ch) // 2, 0)
        out = img[y0:y0 + min(ch, h), x0:x0 + min(cw, w)]
        if out.shape[:2] != (ch, cw):
            out = self._resize(out, cw, ch)
        return out

    def __call__(self, img, cw, ch):
        if self.resize > 0:
            img = self._resize_short(img)
        img = self._crop(img, cw, ch)
        if self.rand_mirror and self.rng.random() < 0.5:
            img = img[:, ::-1]
        img = img.astype(np.float32)
        if self.mean is not None:
            img = img - self.mean
        if self.std is not None:
            img = img / self.std
        return img


def main():
    import cv2
    with open(sys.argv[1]) as f:
        cfg = json.load(f)
    c, ih, iw = cfg["data_shape"]
    label_width = int(cfg.get("label_width", 1))
    slot_imgs = int(cfg["slot_imgs"])
    n_slots = int(cfg["n_slots"])
    img_floats = c * ih * iw
    slot_floats = slot_imgs * (img_floats + label_width)
    shm = shared_memory.SharedMemory(name=cfg["shm_name"])
    buf = np.ndarray((n_slots * slot_floats,), dtype=np.float32,
                     buffer=shm.buf)
    rng = np.random.default_rng(int(cfg.get("seed", 0)))
    aug = Augmenter(cfg.get("aug", {}), rng)
    rec = open(cfg["rec_path"], "rb")

    out = sys.stdout

    def process(order):
        slot = int(order["slot"])
        base = slot * slot_floats
        imgs = buf[base:base + slot_imgs * img_floats].reshape(
            slot_imgs, c, ih, iw)
        labs = buf[base + slot_imgs * img_floats:
                   base + slot_floats].reshape(slot_imgs, label_width)
        try:
            for k, off in enumerate(order["items"]):
                label, body = _unpack(_read_record(rec, off))
                img = cv2.imdecode(np.frombuffer(body, np.uint8),
                                   cv2.IMREAD_COLOR)
                if img is None:
                    raise ValueError(f"undecodable image at offset {off}")
                img = img[:, :, ::-1]                 # BGR -> RGB
                img = aug(img, iw, ih)
                if img.ndim == 2:
                    img = img[:, :, None]
                imgs[k] = img.transpose(2, 0, 1)      # HWC -> CHW
                lab = np.atleast_1d(np.asarray(label, np.float32))
                labs[k, :] = 0.0
                labs[k, :min(label_width, lab.size)] = lab[:label_width]
            out.write(json.dumps({"slot": slot,
                                  "n": len(order["items"])}) + "\n")
        except Exception as e:                        # report, don't die
            out.write(json.dumps({"slot": slot, "error": str(e)}) + "\n")

    for line in sys.stdin:
        req = json.loads(line)
        if req.get("cmd") == "quit":
            break
        # chunked submission: one stdin line may carry several slot
        # orders (parent amortizes json+pipe overhead across batches);
        # replies stay one line per order, oldest first
        for order in req.get("orders") or (req,):
            process(order)
        out.flush()
    shm.close()
    rec.close()


if __name__ == "__main__":
    main()
