"""Image pipeline: decode, augmenters, ImageIter (reference:
python/mxnet/image.py, 559 LoC + the C++ src/io/ pipeline).

The reference's high-throughput path is a C++ OpenCV decode+augment chain;
here decode is cv2/PIL (gated) feeding numpy, with augmenters as pure
functions. ImageRecordIter is provided over the byte-compatible RecordIO
reader with a thread pool for decode (the C++ pipeline's replacement; wrap
in PrefetchingIter for the background-producer behavior).
"""
from __future__ import annotations

import logging
import os
import random as pyrandom
from concurrent.futures import ThreadPoolExecutor

import numpy as np

from .base import MXNetError
from .ndarray import NDArray, array
from .io import DataIter, DataBatch, DataDesc
from . import recordio
from . import telemetry as _telemetry


def _cv2():
    try:
        import cv2
        return cv2
    except ImportError:
        return None


def _imdecode_np(buf, flag=1, to_rgb=True):
    """Decode to a host numpy array (the pipeline-internal path: the hot
    decode loop must never bounce pixels through device buffers)."""
    cv2 = _cv2()
    if cv2 is not None:
        img = cv2.imdecode(np.frombuffer(buf, dtype=np.uint8), flag)
        if img is None:
            raise MXNetError("cannot decode image")
        if to_rgb and img.ndim == 3:
            img = img[..., ::-1]
        return np.ascontiguousarray(img)
    try:
        from PIL import Image
        import io as _io
        img = np.asarray(Image.open(_io.BytesIO(buf)).convert("RGB"))
        return img
    except ImportError:
        raise MXNetError("imdecode requires cv2 or PIL")


def imdecode(buf, flag=1, to_rgb=True, **kwargs):
    """Decode an image byte buffer -> (H, W, C) NDArray.
    reference: image.py imdecode (mx.img)."""
    return array(_imdecode_np(buf, flag, to_rgb))


def _asnp(img):
    return img.asnumpy() if isinstance(img, NDArray) else np.asarray(img)


def _resize_short_np(src, size, interp=2):
    img = _asnp(src)
    h, w = img.shape[:2]
    if h > w:
        new_h, new_w = size * h // w, size
    else:
        new_h, new_w = size, size * w // h
    return _resize(img, new_w, new_h, interp)


def scale_down(src_size, size):
    """Shrink a crop size to fit inside the image (reference:
    image.py:62-70, aspect preserved)."""
    w, h = size
    sw, sh = src_size
    if sh < h:
        w, h = float(w * sh) / h, sh
    if sw < w:
        w, h = sw, float(h * sw) / w
    return int(w), int(h)


def resize_short(src, size, interp=2):
    """Resize shorter edge to `size`. reference: image.py resize_short."""
    return array(_resize_short_np(src, size, interp))


def _resize(img, w, h, interp=2):
    cv2 = _cv2()
    if cv2 is not None:
        return cv2.resize(img, (w, h), interpolation=interp)
    from PIL import Image
    return np.asarray(Image.fromarray(img.astype(np.uint8)).resize((w, h)))


def _fixed_crop_np(src, x0, y0, w, h, size=None, interp=2):
    img = _asnp(src)
    out = img[y0:y0 + h, x0:x0 + w]
    if size is not None and (w, h) != size:
        out = _resize(out, size[0], size[1], interp)
    return out


def fixed_crop(src, x0, y0, w, h, size=None, interp=2):
    return array(_fixed_crop_np(src, x0, y0, w, h, size, interp))


def _random_crop_np(src, size, interp=2):
    img = _asnp(src)
    h, w = img.shape[:2]
    new_w, new_h = size
    x0 = pyrandom.randint(0, max(w - new_w, 0))
    y0 = pyrandom.randint(0, max(h - new_h, 0))
    out = _fixed_crop_np(img, x0, y0, min(new_w, w), min(new_h, h), size,
                         interp)
    return out, (x0, y0, new_w, new_h)


def random_crop(src, size, interp=2):
    out, coords = _random_crop_np(src, size, interp)
    return array(out), coords


def _center_crop_np(src, size, interp=2):
    img = _asnp(src)
    h, w = img.shape[:2]
    new_w, new_h = size
    x0 = max((w - new_w) // 2, 0)
    y0 = max((h - new_h) // 2, 0)
    out = _fixed_crop_np(img, x0, y0, min(new_w, w), min(new_h, h), size,
                         interp)
    return out, (x0, y0, new_w, new_h)


def center_crop(src, size, interp=2):
    out, coords = _center_crop_np(src, size, interp)
    return array(out), coords


def _random_size_crop_np(src, size, min_area=0.08, ratio=(3 / 4.0, 4 / 3.0),
                         interp=2):
    img = _asnp(src)
    h, w = img.shape[:2]
    area = h * w
    for _ in range(10):
        target_area = pyrandom.uniform(min_area, 1.0) * area
        log_ratio = (np.log(ratio[0]), np.log(ratio[1]))
        aspect = np.exp(pyrandom.uniform(*log_ratio))
        new_w = int(round(np.sqrt(target_area * aspect)))
        new_h = int(round(np.sqrt(target_area / aspect)))
        if new_w <= w and new_h <= h:
            x0 = pyrandom.randint(0, w - new_w)
            y0 = pyrandom.randint(0, h - new_h)
            return _fixed_crop_np(img, x0, y0, new_w, new_h, size,
                                  interp), (x0, y0, new_w, new_h)
    return _center_crop_np(src, size, interp)


def _color_normalize_np(src, mean, std=None):
    img = _asnp(src).astype(np.float32)
    img = img - _asnp(mean)
    if std is not None:
        img = img / _asnp(std)
    return img


def color_normalize(src, mean, std=None):
    return array(_color_normalize_np(src, mean, std))


# ------------------------------------------------------------- augmenters
def ResizeAug(size, interp=2):
    def aug(src):
        return [_resize_short_np(src, size, interp)]
    return aug


def RandomCropAug(size, interp=2):
    def aug(src):
        return [_random_crop_np(src, size, interp)[0]]
    return aug


def RandomSizedCropAug(size, min_area=0.08, ratio=(3 / 4.0, 4 / 3.0),
                       interp=2):
    def aug(src):
        return [_random_size_crop_np(src, size, min_area, ratio, interp)[0]]
    return aug


def CenterCropAug(size, interp=2):
    def aug(src):
        return [_center_crop_np(src, size, interp)[0]]
    return aug


def RandomOrderAug(ts):
    def aug(src):
        srcs = [src]
        ts_shuffled = list(ts)
        pyrandom.shuffle(ts_shuffled)
        for t in ts_shuffled:
            srcs = sum([t(s) for s in srcs], [])
        return srcs
    return aug


def ColorJitterAug(brightness, contrast, saturation):
    coef = np.array([[[0.299, 0.587, 0.114]]], dtype=np.float32)

    def aug(src):
        img = _asnp(src).astype(np.float32)
        if brightness > 0:
            alpha = 1.0 + pyrandom.uniform(-brightness, brightness)
            img = img * alpha
        if contrast > 0:
            alpha = 1.0 + pyrandom.uniform(-contrast, contrast)
            gray = (img * coef).sum(axis=2, keepdims=True)
            img = img * alpha + gray.mean() * (1 - alpha)
        if saturation > 0:
            alpha = 1.0 + pyrandom.uniform(-saturation, saturation)
            gray = (img * coef).sum(axis=2, keepdims=True)
            img = img * alpha + gray * (1 - alpha)
        return [img]
    return aug


def LightingAug(alphastd, eigval, eigvec):
    def aug(src):
        img = _asnp(src).astype(np.float32)
        alpha = np.random.normal(0, alphastd, size=(3,))
        rgb = np.dot(_asnp(eigvec) * alpha, _asnp(eigval))
        return [img + rgb]
    return aug


def ColorNormalizeAug(mean, std):
    def aug(src):
        return [_color_normalize_np(src, mean, std)]
    return aug


def HorizontalFlipAug(p):
    def aug(src):
        if pyrandom.random() < p:
            return [_asnp(src)[:, ::-1]]
        return [src]
    return aug


def CastAug():
    def aug(src):
        return [_asnp(src).astype(np.float32)]
    return aug


def CreateAugmenter(data_shape, resize=0, rand_crop=False, rand_resize=False,
                    rand_mirror=False, mean=None, std=None, brightness=0,
                    contrast=0, saturation=0, pca_noise=0, inter_method=2):
    """reference: image.py CreateAugmenter."""
    auglist = []
    if resize > 0:
        auglist.append(ResizeAug(resize, inter_method))
    crop_size = (data_shape[2], data_shape[1])
    if rand_resize:
        assert rand_crop
        auglist.append(RandomSizedCropAug(crop_size, 0.3, (3.0 / 4.0,
                                                           4.0 / 3.0),
                                          inter_method))
    elif rand_crop:
        auglist.append(RandomCropAug(crop_size, inter_method))
    else:
        auglist.append(CenterCropAug(crop_size, inter_method))
    if rand_mirror:
        auglist.append(HorizontalFlipAug(0.5))
    auglist.append(CastAug())
    if brightness or contrast or saturation:
        auglist.append(ColorJitterAug(brightness, contrast, saturation))
    if pca_noise > 0:
        eigval = np.array([55.46, 4.794, 1.148])
        eigvec = np.array([[-0.5675, 0.7192, 0.4009],
                           [-0.5808, -0.0045, -0.8140],
                           [-0.5836, -0.6948, 0.4203]])
        auglist.append(LightingAug(pca_noise, eigval, eigvec))
    if mean is True:
        mean = np.array([123.68, 116.28, 103.53])
    if std is True:
        std = np.array([58.395, 57.12, 57.375])
    if mean is not None:
        assert std is not None
        auglist.append(ColorNormalizeAug(mean, std))
    return auglist


class ImageIter(DataIter):
    """Pure-python image iterator over .rec or .lst/raw images.
    reference: image.py ImageIter; decode parallelized with a thread pool
    (the reference's OMP decode loop, iter_image_recordio_2.cc:28)."""

    def __init__(self, batch_size, data_shape, label_width=1,
                 path_imgrec=None, path_imglist=None, path_root=None,
                 path_imgidx=None, shuffle=False, part_index=0, num_parts=1,
                 aug_list=None, imgrec=None, data_name="data",
                 label_name="softmax_label", num_threads=4, **kwargs):
        super().__init__(batch_size)
        assert path_imgrec or path_imglist or imgrec
        if path_imgrec:
            if path_imgidx:
                self.imgrec = recordio.MXIndexedRecordIO(path_imgidx,
                                                         path_imgrec, "r")
                self.imgidx = list(self.imgrec.keys)
            else:
                self.imgrec = recordio.MXRecordIO(path_imgrec, "r")
                self.imgidx = None
        else:
            self.imgrec = imgrec
            self.imgidx = None

        self.imglist = None
        if path_imglist:
            with open(path_imglist) as fin:
                imglist = {}
                imgkeys = []
                for line in fin:
                    line = line.strip().split("\t")
                    label = np.array(line[1:-1], dtype=np.float32)
                    key = int(line[0])
                    imglist[key] = (label, line[-1])
                    imgkeys.append(key)
                self.imglist = imglist
                self.imgidx = imgkeys
        self.path_root = path_root

        self.shuffle = shuffle
        if num_parts > 1 and self.imgidx is not None:
            n = len(self.imgidx) // num_parts
            self.imgidx = self.imgidx[part_index * n:(part_index + 1) * n]
        self.data_shape = tuple(data_shape)
        self.label_width = label_width
        self.aug_list = aug_list if aug_list is not None else \
            CreateAugmenter(data_shape)
        self.data_name = data_name
        self.label_name = label_name
        self._pool = ThreadPoolExecutor(max_workers=num_threads)
        self.cur = 0
        self.seq = self.imgidx
        self.reset()

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
        if self.shuffle and self.seq is not None:
            pyrandom.shuffle(self.seq)
        if self.imgrec is not None:
            self.imgrec.reset()
        self.cur = 0

    def _read_one(self, i=None):
        if self.seq is not None and self.imglist is None:
            s = self.imgrec.read_idx(self.seq[i])
            header, img_bytes = recordio.unpack(s)
            label = header.label
        elif self.imglist is not None:
            label, fname = self.imglist[self.seq[i]]
            with open(os.path.join(self.path_root, fname), "rb") as f:
                img_bytes = f.read()
        else:
            s = self.imgrec.read()
            if s is None:
                return None
            header, img_bytes = recordio.unpack(s)
            label = header.label
        return label, img_bytes

    def _decode_augment(self, item):
        label, img_bytes = item
        img = _imdecode_np(img_bytes)
        for aug in self.aug_list:
            img = aug(img)[0]
        arr = _asnp(img).astype(np.float32)
        if arr.ndim == 2:
            arr = arr[:, :, None]
        arr = arr.transpose(2, 0, 1)  # HWC -> CHW (reference layout)
        return arr, label

    def next(self):
        items = []
        for _ in range(self.batch_size):
            if self.seq is not None:
                if self.cur >= len(self.seq):
                    break
                item = self._read_one(self.cur)
                self.cur += 1
            else:
                item = self._read_one()
                if item is None:
                    break
            items.append(item)
        if not items:
            raise StopIteration
        pad = self.batch_size - len(items)
        if _telemetry.enabled():
            _telemetry.counter("io.batches", iter=type(self).__name__).inc()
            _telemetry.counter("io.images_decoded").inc(len(items))
            decode_span = _telemetry.span(
                "io.decode", _hist="io.decode.seconds", images=len(items))
        else:
            decode_span = _telemetry.null_span
        with decode_span:
            decoded = list(self._pool.map(self._decode_augment, items))
        data = np.zeros((self.batch_size,) + self.data_shape,
                        dtype=np.float32)
        labels = np.zeros((self.batch_size, self.label_width),
                          dtype=np.float32)
        for i, (arr, label) in enumerate(decoded):
            data[i] = arr
            lab = np.atleast_1d(np.asarray(label, dtype=np.float32))
            labels[i, :self.label_width] = lab[:self.label_width]
        if self.label_width == 1:
            labels = labels[:, 0]
        return DataBatch([array(data)], [array(labels)], pad=pad)


# Process-wide decode-pipeline choice from the one-shot throughput
# probe: None = not probed yet, "mp" / "threads" afterwards. The probe
# runs once because the answer is a property of the host (cores, IPC
# cost), not of any one iterator.
_AUTO_PIPELINE = {"choice": None}


def _probe_img_per_sec(it, n_batches, batch_size):
    """Measured decode throughput over a few batches (img/s)."""
    import time
    n = 0
    t0 = time.perf_counter()
    try:
        for _ in range(n_batches):
            it.next()
            n += batch_size
    except StopIteration:
        pass
    dt = time.perf_counter() - t0
    return n / dt if dt > 0 else 0.0


def ImageRecordIter(path_imgrec, data_shape, batch_size, path_imgidx=None,
                    shuffle=False, rand_crop=False, rand_mirror=False,
                    mean_r=0, mean_g=0, mean_b=0, std_r=1, std_g=1, std_b=1,
                    resize=0, part_index=0, num_parts=1, prefetch=True,
                    data_name="data", label_name="softmax_label",
                    num_workers=None, seed=0, **kwargs):
    """Factory matching the reference's ImageRecordIter params
    (reference: iter_image_recordio_2.cc registration :559-579).

    The standard param-driven augmentation set routes to the
    multiprocess decode pipeline (mp_decode.py — the analog of the
    reference's OMP-parallel C++ parser); anything it can't express
    falls back to the in-process thread-pool ImageIter. Set
    ``num_workers=0`` (or MXNET_DECODE_WORKERS=0) to force the
    fallback.

    When neither ``num_workers`` nor ``MXNET_DECODE_WORKERS`` picks a
    pipeline, the choice is *measured*: single-core hosts go straight to
    the thread pool (the mp pipeline only adds IPC there), and multi-core
    hosts run a one-shot throughput probe of both pipelines, keeping the
    faster."""
    mean = None
    std = None
    if mean_r or mean_g or mean_b:
        mean = np.array([mean_r, mean_g, mean_b])
    if std_r != 1 or std_g != 1 or std_b != 1:
        std = np.array([std_r, std_g, std_b])

    env_workers = os.environ.get("MXNET_DECODE_WORKERS")
    if num_workers is None and env_workers is not None:
        num_workers = int(env_workers)
    mp_ok = (num_workers != 0
             and set(kwargs) <= {"label_width"}
             and path_imgrec is not None)

    def _threaded():
        aug_list = CreateAugmenter(data_shape, resize=resize,
                                   rand_crop=rand_crop,
                                   rand_mirror=rand_mirror,
                                   mean=mean, std=std)
        return ImageIter(batch_size, data_shape, path_imgrec=path_imgrec,
                         path_imgidx=path_imgidx, shuffle=shuffle,
                         part_index=part_index, num_parts=num_parts,
                         aug_list=aug_list, data_name=data_name,
                         label_name=label_name, **kwargs)

    def _mp():
        from .mp_decode import MPImageRecordIter
        return MPImageRecordIter(
            path_imgrec, data_shape, batch_size, path_imgidx=path_imgidx,
            label_width=kwargs.get("label_width", 1), shuffle=shuffle,
            part_index=part_index, num_parts=num_parts,
            aug_params={"resize": resize, "rand_crop": rand_crop,
                        "rand_mirror": rand_mirror,
                        "mean": None if mean is None else mean.tolist(),
                        "std": None if std is None else std.tolist()},
            num_workers=num_workers, seed=seed,
            data_name=data_name, label_name=label_name)

    # auto selection: nobody pinned a pipeline, so measure instead of
    # assuming the mp path wins (it loses on low-core hosts)
    if mp_ok and num_workers is None:
        if (os.cpu_count() or 1) <= 1:
            mp_ok = False
        else:
            if _AUTO_PIPELINE["choice"] is None:
                probe_n = max(2, 128 // batch_size)
                mp_it = _mp()
                try:
                    mp_rate = _probe_img_per_sec(mp_it, probe_n, batch_size)
                finally:
                    mp_it.close()
                th_rate = _probe_img_per_sec(_threaded(), probe_n,
                                             batch_size)
                _AUTO_PIPELINE["choice"] = \
                    "mp" if mp_rate >= th_rate else "threads"
                logging.info(
                    "ImageRecordIter autotune: mp %.0f img/s vs threads "
                    "%.0f img/s -> %s", mp_rate, th_rate,
                    _AUTO_PIPELINE["choice"])
            mp_ok = _AUTO_PIPELINE["choice"] == "mp"

    from .io import PrefetchingIter
    it = _mp() if mp_ok else _threaded()
    return PrefetchingIter(it) if prefetch else it


# ---------------------------------------------------------------------------
# detection-aware augmenters + iterator (reference:
# src/io/image_det_aug_default.cc:1-667, iter_image_det_recordio.cc:578).
# Det augmenters transform (image, label) together; label is a (num_obj, 5)
# float array of rows [cls_id, x1, y1, x2, y2] with coordinates normalized
# to [0, 1] and cls_id = -1 marking padding rows.
# ---------------------------------------------------------------------------
def _det_valid(label):
    return label[:, 0] >= 0


def DetHorizontalFlipAug(p):
    """Mirror image and boxes together (reference: DefaultImageDetAugmenter
    rand_mirror_prob)."""
    def aug(src, label):
        if pyrandom.random() < p:
            img = _asnp(src)[:, ::-1]
            lab = label.copy()
            v = _det_valid(lab)
            x1 = lab[:, 1].copy()
            lab[:, 1] = np.where(v, 1.0 - lab[:, 3], lab[:, 1])
            lab[:, 3] = np.where(v, 1.0 - x1, lab[:, 3])
            return img, lab
        return src, label
    return aug


def DetRandomCropAug(min_object_covered=0.3, aspect_ratio_range=(0.75, 1.33),
                     area_range=(0.3, 1.0), max_attempts=25):
    """Box-aware random crop: a sampled crop is accepted only if it keeps
    at least one object center and covers >= min_object_covered of each
    kept object (reference: det_aug crop_strategies)."""
    def aug(src, label):
        img = _asnp(src)
        h, w = img.shape[:2]
        valid = _det_valid(label)
        if not valid.any():
            return src, label
        for _ in range(max_attempts):
            area = pyrandom.uniform(*area_range)
            aspect = pyrandom.uniform(*aspect_ratio_range)
            cw = min(1.0, np.sqrt(area * aspect))
            ch = min(1.0, np.sqrt(area / aspect))
            cx0 = pyrandom.uniform(0, 1.0 - cw)
            cy0 = pyrandom.uniform(0, 1.0 - ch)
            cx1, cy1 = cx0 + cw, cy0 + ch
            centers_x = (label[:, 1] + label[:, 3]) / 2
            centers_y = (label[:, 2] + label[:, 4]) / 2
            keep = valid & (centers_x > cx0) & (centers_x < cx1) & \
                (centers_y > cy0) & (centers_y < cy1)
            if not keep.any():
                continue
            # coverage of each kept box by the crop
            ix1 = np.maximum(label[:, 1], cx0)
            iy1 = np.maximum(label[:, 2], cy0)
            ix2 = np.minimum(label[:, 3], cx1)
            iy2 = np.minimum(label[:, 4], cy1)
            inter = np.clip(ix2 - ix1, 0, None) * np.clip(iy2 - iy1, 0,
                                                          None)
            box_area = (label[:, 3] - label[:, 1]) * \
                (label[:, 4] - label[:, 2])
            cov = np.where(box_area > 0, inter / np.maximum(box_area, 1e-8),
                           0.0)
            if (cov[keep] < min_object_covered).any():
                continue
            px0, py0 = int(cx0 * w), int(cy0 * h)
            px1, py1 = max(px0 + 1, int(cx1 * w)), max(py0 + 1, int(cy1 * h))
            out = img[py0:py1, px0:px1]
            lab = label.copy()
            lab[:, 0] = np.where(keep, lab[:, 0], -1.0)
            for c, (lo, span) in ((1, (cx0, cw)), (3, (cx0, cw)),
                                  (2, (cy0, ch)), (4, (cy0, ch))):
                lab[:, c] = np.clip((lab[:, c] - lo) / span, 0.0, 1.0)
            return out, lab
        return src, label
    return aug


def DetRandomPadAug(aspect_ratio_range=(0.75, 1.33), area_range=(1.0, 2.0),
                    max_attempts=25, fill=127):
    """Place the image on a larger filled canvas, shrinking boxes
    accordingly (reference: det_aug rand_pad_prob/pad strategies)."""
    def aug(src, label):
        img = _asnp(src)
        h, w = img.shape[:2]
        for _ in range(max_attempts):
            area = pyrandom.uniform(*area_range)
            aspect = pyrandom.uniform(*aspect_ratio_range)
            nw = np.sqrt(area * aspect)
            nh = np.sqrt(area / aspect)
            if nw < 1.0 or nh < 1.0:
                continue
            ph, pw = int(round(h * nh)), int(round(w * nw))
            y0 = pyrandom.randint(0, ph - h)
            x0 = pyrandom.randint(0, pw - w)
            canvas = np.full((ph, pw) + img.shape[2:], fill,
                             dtype=img.dtype)
            canvas[y0:y0 + h, x0:x0 + w] = img
            lab = label.copy()
            v = _det_valid(lab)
            lab[:, 1] = np.where(v, (lab[:, 1] * w + x0) / pw, lab[:, 1])
            lab[:, 3] = np.where(v, (lab[:, 3] * w + x0) / pw, lab[:, 3])
            lab[:, 2] = np.where(v, (lab[:, 2] * h + y0) / ph, lab[:, 2])
            lab[:, 4] = np.where(v, (lab[:, 4] * h + y0) / ph, lab[:, 4])
            return canvas, lab
        return src, label
    return aug


def DetResizeAug(size, interp=2):
    """Force resize to (w, h) = size — boxes are normalized, unchanged."""
    def aug(src, label):
        img = _asnp(src)
        cv2 = _cv2()
        if cv2 is not None:
            out = cv2.resize(img, size, interpolation=interp)
        else:
            ys = (np.linspace(0, img.shape[0] - 1, size[1])).astype(int)
            xs = (np.linspace(0, img.shape[1] - 1, size[0])).astype(int)
            out = img[ys][:, xs]
        return out, label
    return aug


def _det_wrap(color_aug):
    """Lift a classification (image-only) augmenter to det signature."""
    def aug(src, label):
        return color_aug(src)[0], label
    return aug


def CreateDetAugmenter(data_shape, resize=0, rand_crop=0, rand_pad=0,
                       rand_mirror=False, mean=None, std=None, brightness=0,
                       contrast=0, saturation=0, pca_noise=0,
                       min_object_covered=0.3,
                       aspect_ratio_range=(0.75, 1.33),
                       area_range=(0.3, 3.0), max_attempts=25,
                       pad_val=127, inter_method=2):
    """reference: CreateDetAugmenter (image_det_aug_default.cc params)."""
    auglist = []
    if resize > 0:
        # shorter-edge resize BEFORE crops/pads, like the reference —
        # boxes are normalized so only the pixels change
        def shorter_edge(src, label, _s=resize, _i=inter_method):
            return _resize_short_np(src, _s, _i), label
        auglist.append(shorter_edge)
    if rand_crop > 0:
        crop = DetRandomCropAug(min_object_covered, aspect_ratio_range,
                                (min(area_range[0], 1.0),
                                 min(area_range[1], 1.0)), max_attempts)
        p = rand_crop

        def maybe_crop(src, label, _crop=crop, _p=p):
            if pyrandom.random() < _p:
                return _crop(src, label)
            return src, label
        auglist.append(maybe_crop)
    if rand_pad > 0:
        pad = DetRandomPadAug(aspect_ratio_range,
                              (max(area_range[0], 1.0),
                               max(area_range[1], 1.0)),
                              max_attempts, pad_val)
        p = rand_pad

        def maybe_pad(src, label, _pad=pad, _p=p):
            if pyrandom.random() < _p:
                return _pad(src, label)
            return src, label
        auglist.append(maybe_pad)
    auglist.append(DetResizeAug((data_shape[2], data_shape[1]),
                                inter_method))
    if rand_mirror:
        auglist.append(DetHorizontalFlipAug(0.5))
    auglist.append(_det_wrap(CastAug()))
    if brightness or contrast or saturation:
        auglist.append(_det_wrap(ColorJitterAug(brightness, contrast,
                                                saturation)))
    if pca_noise > 0:
        eigval = np.array([55.46, 4.794, 1.148])
        eigvec = np.array([[-0.5675, 0.7192, 0.4009],
                           [-0.5808, -0.0045, -0.8140],
                           [-0.5836, -0.6948, 0.4203]])
        auglist.append(_det_wrap(LightingAug(pca_noise, eigval, eigvec)))
    if mean is True:
        mean = np.array([123.68, 116.28, 103.53])
    if std is True:
        std = np.array([58.395, 57.12, 57.375])
    if mean is not None:
        auglist.append(_det_wrap(ColorNormalizeAug(mean, std)))
    return auglist


class ImageDetIter(DataIter):
    """Detection iterator (reference: ImageDetRecordIter,
    iter_image_det_recordio.cc:578): yields data (N, C, H, W) and padded
    label (N, max_obj, 5). Sources: in-memory (images, labels) lists or a
    RecordIO pack via ``path_imgrec`` where each record's label is a flat
    [cls, x1, y1, x2, y2] * k vector."""

    def __init__(self, batch_size, data_shape, images=None, labels=None,
                 path_imgrec=None, shuffle=False, aug_list=None,
                 max_objects=None, data_name="data", label_name="label",
                 **kwargs):
        super().__init__(batch_size)
        self._data_shape = tuple(data_shape)
        if path_imgrec is not None:
            # hold compressed buffers, decode per batch (a full detection
            # pack decoded up front would not fit in host memory; the
            # classification ImageIter streams the same way)
            rec = recordio.MXRecordIO(path_imgrec, "r")
            images, labels = [], []
            while True:
                item = rec.read()
                if item is None:
                    break
                header, img_buf = recordio.unpack(item)
                flat = np.asarray(header.label, dtype=np.float32).reshape(
                    -1, 5)
                images.append(img_buf)
                labels.append(flat)
            rec.close()
        if images is None or labels is None:
            raise MXNetError("ImageDetIter needs images+labels or "
                             "path_imgrec")
        self._images = list(images)
        self._labels = [np.asarray(l, dtype=np.float32).reshape(-1, 5)
                        for l in labels]
        self._shuffle = shuffle
        self._aug = aug_list if aug_list is not None else \
            CreateDetAugmenter(data_shape)
        self._max_obj = max_objects or max(
            (l.shape[0] for l in self._labels), default=1)
        self._order = list(range(len(self._images)))
        self._pos = 0
        self.data_name, self.label_name = data_name, label_name
        self.reset()

    @property
    def provide_data(self):
        return [DataDesc(self.data_name,
                         (self.batch_size,) + self._data_shape)]

    @property
    def provide_label(self):
        return [DataDesc(self.label_name,
                         (self.batch_size, self._max_obj, 5))]

    def reset(self):
        self._pos = 0
        if self._shuffle:
            pyrandom.shuffle(self._order)

    def next(self):
        if self._pos >= len(self._order):
            raise StopIteration
        n = self.batch_size
        data = np.zeros((n,) + self._data_shape, dtype=np.float32)
        label = np.full((n, self._max_obj, 5), -1.0, dtype=np.float32)
        pad = 0
        for i in range(n):
            if self._pos >= len(self._order):
                pad += 1
                continue
            idx = self._order[self._pos]
            self._pos += 1
            img = self._images[idx]
            if isinstance(img, (bytes, bytearray)):
                img = _imdecode_np(img)
            lab = self._labels[idx].copy()
            for aug in self._aug:
                img, lab = aug(img, lab)
            img = _asnp(img).astype(np.float32)
            data[i] = img.transpose(2, 0, 1)
            k = min(lab.shape[0], self._max_obj)
            label[i, :k] = lab[:k]
        return DataBatch([array(data)], [array(label)], pad=pad)


def random_size_crop(src, size, min_area=0.08, ratio=(3 / 4.0, 4 / 3.0),
                     interp=2):
    out, coords = _random_size_crop_np(src, size, min_area, ratio, interp)
    return array(out), coords
