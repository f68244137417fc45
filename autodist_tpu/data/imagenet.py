"""ImageNet-class image pipeline: image tree -> uint8 shards -> device augment.

Counterpart of the reference CNN benchmark's real input path
(``examples/benchmark/imagenet.py:219-229`` ``input_fn`` reading tfrecords
through ``utils/imagenet_preprocessing.py``: decode, sampled crop, flip,
resize, mean subtraction). The TPU-first redesign splits the work by where it
runs best:

- **Offline prep** (:func:`prepare_image_shards`): decode + aspect-preserving
  resize + center crop to a fixed ``record_size`` square, stored as uint8 NHWC
  ``images-*.npy`` / int32 ``labels-*.npy`` row-aligned shards — the files the
  native ``DataLoader(files=...)`` memory-maps and gathers off the GIL. uint8
  records keep disk/page-cache bandwidth 4x below float32.
- **Train-time augmentation ON DEVICE** (:func:`augment_images`): random
  ``image_size`` crop out of the record + horizontal flip + channel-mean
  subtraction + cast, all inside the jitted train step (fused by XLA, runs at
  HBM speed). Crop offsets and flip bits are drawn per batch on the host
  (:class:`AugmentingBatcher`) — two tiny int arrays, so the step stays a pure
  function of its inputs and masking determinism is a host seed.

The reference's *bbox-sampled* distorted crop resizes a different-shaped
window per example — per-example dynamic shapes, which XLA cannot tile onto
the MXU. The fixed-record random-crop + flip here is the classic alternative
("VGG preprocessing" in the reference's own naming,
``imagenet_preprocessing.py:26-31``) and keeps every shape static; eval uses
the standard center crop, no flip.
"""

import glob as globlib
import json
import os
from typing import Dict, Iterator, List, Optional, Sequence, Tuple

import numpy as np

from autodist_tpu.utils import logging

# Reference imagenet_preprocessing.py:53-57 (RGB means; subtraction only, no
# std scaling — kept for parity).
CHANNEL_MEANS = (123.68, 116.78, 103.94)

META_NAME = "images-meta.json"
_EXTS = (".jpg", ".jpeg", ".png", ".bmp", ".webp")


def _iter_image_files(src_dir: str) -> Iterator[Tuple[str, str]]:
    """Yield (class_name, path) over a ``src_dir/<class>/<image>`` tree in
    deterministic (sorted) order."""
    classes = sorted(d for d in os.listdir(src_dir)
                     if os.path.isdir(os.path.join(src_dir, d)))
    if not classes:
        raise ValueError(f"{src_dir!r} has no class subdirectories")
    for cls in classes:
        for name in sorted(os.listdir(os.path.join(src_dir, cls))):
            if name.lower().endswith(_EXTS):
                yield cls, os.path.join(src_dir, cls, name)


def _decode_record(path: str, record_size: int) -> np.ndarray:
    """Decode one image file -> uint8 [record_size, record_size, 3]:
    aspect-preserving resize (short side = record_size, the reference's
    _RESIZE_MIN step) then center crop."""
    from PIL import Image
    with Image.open(path) as im:
        im = im.convert("RGB")
        w, h = im.size
        scale = record_size / min(w, h)
        nw, nh = max(record_size, round(w * scale)), max(record_size, round(h * scale))
        im = im.resize((nw, nh), Image.BILINEAR)
        left, top = (nw - record_size) // 2, (nh - record_size) // 2
        im = im.crop((left, top, left + record_size, top + record_size))
        return np.asarray(im, np.uint8)


def prepare_image_shards(src_dir: str, directory: str, record_size: int = 256,
                         rows_per_shard: int = 1024,
                         shuffle_seed: Optional[int] = 0) -> Dict[str, List[str]]:
    """Decode a ``src_dir/<class>/<image>`` tree into row-aligned uint8
    ``images-*.npy`` + int32 ``labels-*.npy`` shards under ``directory``.

    Labels are the sorted class-directory index. Files are shuffled once
    before sharding (seeded; ``shuffle_seed=None`` keeps tree order) so a
    sequential reader still sees mixed classes. Memory stays bounded at one
    shard buffer. Writes an ``images-meta.json`` sidecar (record_size,
    classes, rows) the training side validates against. Returns the
    ``DataLoader(files=...)`` dict.
    """
    if record_size < 8:
        raise ValueError("record_size must be >= 8")
    if rows_per_shard < 1:
        raise ValueError("rows_per_shard must be >= 1")
    entries = list(_iter_image_files(src_dir))
    if not entries:
        raise ValueError(f"no image files under {src_dir!r}")
    classes = sorted({cls for cls, _ in entries})
    cls_id = {c: i for i, c in enumerate(classes)}
    if shuffle_seed is not None:
        np.random.RandomState(shuffle_seed).shuffle(entries)

    os.makedirs(directory, exist_ok=True)
    for key in ("images", "labels"):
        for stale in globlib.glob(os.path.join(globlib.escape(directory),
                                               f"{key}-*.npy")):
            os.remove(stale)

    img_buf = np.empty((rows_per_shard, record_size, record_size, 3), np.uint8)
    lab_buf = np.empty((rows_per_shard,), np.int32)
    n_buf = 0
    paths: Dict[str, List[str]] = {"images": [], "labels": []}

    def flush():
        nonlocal n_buf
        if n_buf == 0:
            return
        for key, buf in (("images", img_buf), ("labels", lab_buf)):
            path = os.path.join(directory, f"{key}-{len(paths[key]):05d}.npy")
            np.save(path, buf[:n_buf])
            paths[key].append(path)
        n_buf = 0

    n_rows = 0
    for cls, path in entries:
        img_buf[n_buf] = _decode_record(path, record_size)
        lab_buf[n_buf] = cls_id[cls]
        n_buf += 1
        n_rows += 1
        if n_buf == rows_per_shard:
            flush()
    flush()

    with open(os.path.join(directory, META_NAME), "w") as f:
        json.dump({"record_size": record_size, "rows": n_rows,
                   "classes": classes}, f, indent=1)
    logging.info("Prepared %d image records (%dx%d uint8, %d classes) across "
                 "%d shards in %s", n_rows, record_size, record_size,
                 len(classes), len(paths["images"]), directory)
    return paths


def read_meta(directory: str) -> Optional[dict]:
    path = os.path.join(directory, META_NAME)
    if not os.path.exists(path):
        return None
    with open(path) as f:
        return json.load(f)


def open_image_loader(directory: str, batch_size: int, **loader_kw):
    """DataLoader over a prepared shard directory (+ its meta)."""
    from autodist_tpu.data.loader import DataLoader
    meta = read_meta(directory)
    if meta is None:
        raise FileNotFoundError(f"no {META_NAME} under {directory!r} "
                                f"(prepare_image_shards writes one)")
    files = {k: sorted(globlib.glob(os.path.join(globlib.escape(directory),
                                                 f"{k}-*.npy")))
             for k in ("images", "labels")}
    return DataLoader(files=files, batch_size=batch_size, **loader_kw), meta


def augment_images(images, crop_yx, flip, image_size: int, dtype=None):
    """Device-side train augmentation: per-example ``image_size`` crop at
    ``crop_yx``, horizontal flip where ``flip``, channel-mean subtraction,
    cast. Runs inside the jitted step — XLA fuses it into the input side of
    the first conv. ``images`` uint8 [B, R, R, 3]; returns [B, S, S, 3]."""
    import jax
    import jax.numpy as jnp

    def crop_one(img, yx):
        return jax.lax.dynamic_slice(img, (yx[0], yx[1], 0),
                                     (image_size, image_size, 3))

    x = jax.vmap(crop_one)(images, crop_yx)
    x = jnp.where(flip[:, None, None, None], x[:, :, ::-1, :], x)
    x = x.astype(jnp.float32) - jnp.asarray(CHANNEL_MEANS, jnp.float32)
    return x.astype(dtype) if dtype is not None else x


def make_augmented_loss_fn(model, image_size: int, dtype=None):
    """Classification loss over RAW record batches: augmentation happens in
    the same jit as the model (one fused program, nothing materializes on
    host). Batch keys: ``images`` (uint8 records), ``labels``, ``crop_yx``,
    ``flip`` — the :class:`AugmentingBatcher` layout."""
    import jax
    import jax.numpy as jnp

    def loss_fn(params, batch):
        x = augment_images(batch["images"], batch["crop_yx"], batch["flip"],
                           image_size, dtype)
        logits = model.apply({"params": params}, x)
        logprobs = jax.nn.log_softmax(logits)
        nll = -jnp.take_along_axis(logprobs, batch["labels"][:, None],
                                   axis=-1)[:, 0]
        return nll.mean()

    return loss_fn


class DeviceDatasetCache:
    """HBM-resident record pool with background refresh from disk shards.

    The reference's ``training_dataset_cache`` knob cached the training
    dataset in worker memory "when training data is in remote storage"
    (``examples/benchmark/imagenet.py:219-229``); the TPU-native analogue
    caches uint8 records IN HBM. Every step assembles its batch on device —
    a pool gather + :func:`augment_images` in one jit, so no image bytes
    cross the host link on the critical path — while a trickle of fresh
    records replaces pool slots round-robin, issued ``refresh_interval``
    steps ahead so the host->HBM transfer hides under compute. With a pool
    covering the dataset this converges to full caching (the reference knob's
    semantics); with a smaller pool it is reservoir-style streaming whose
    epoch time is bounded by the link, not the step rate.

    Use :class:`AugmentingBatcher` + ``device_prefetch`` instead when the
    host->device link is fast enough to stream full batches (a real TPU VM's
    PCIe); this class exists for weak links (remote storage).
    """

    #: Default HBM budget for the record pool when ``pool_rows`` is unset —
    #: conservative against a v5e's 16 GB (model + optimizer + activations
    #: own the rest). At record_size 256 this is ~20k records.
    DEFAULT_POOL_BYTES = 4 << 30

    def __init__(self, loader, *, record_size: int, image_size: int,
                 dtype=None, pool_rows: Optional[int] = None,
                 refresh_rows: int = 64, refresh_interval: int = 16,
                 train: bool = True, seed: int = 0):
        import jax
        import jax.numpy as jnp

        if image_size > record_size:
            raise ValueError(f"image_size {image_size} exceeds record_size "
                             f"{record_size}")
        self._loader = loader
        self.image_size = image_size
        self.record_size = record_size
        self.train = train
        self._rng = np.random.Generator(np.random.PCG64(seed))
        if pool_rows is None:
            # Cap the resident pool by an HBM budget, not the dataset size —
            # real-scale datasets (ImageNet: 1.28M records) must stream
            # through a bounded pool, not OOM at startup.
            row_bytes = record_size * record_size * 3
            pool_rows = max(1, self.DEFAULT_POOL_BYTES // row_bytes)
        # The loader serves whole batches (drop-last): a pool larger than the
        # servable row count would fill its tail from the NEXT epoch's batches
        # — duplicate rows in the pool, and (sequential loaders) the dropped
        # tail never cached. Size to whole batches instead.
        servable = loader.n_rows - loader.n_rows % loader.batch_size
        self._rows = min(pool_rows, servable)
        self._buf_imgs: Optional[np.ndarray] = None  # undrained loader rows
        self._buf_labs: Optional[np.ndarray] = None
        self._refresh_rows = min(refresh_rows, self._rows) if refresh_rows else 0
        self._refresh_interval = max(1, refresh_interval)
        self._step = 0
        self._cursor = 0
        self._pending = None  # (device rows, labels, start) issued last tick

        # Fill the pool once through the loader (link-speed, one-time).
        imgs = np.empty((self._rows, record_size, record_size, 3), np.uint8)
        labs = np.empty((self._rows,), np.int32)
        filled = 0
        while filled < self._rows:
            raw = loader.next()
            take = min(len(raw["images"]), self._rows - filled)
            imgs[filled:filled + take] = raw["images"][:take]
            labs[filled:filled + take] = raw["labels"][:take]
            filled += take
        self._pool = jax.device_put(imgs)
        self._labels = labs  # host-side: labels are 4 bytes/row

        out_dtype = dtype or jnp.float32

        def _assemble(pool, idx, crop, flip):
            return augment_images(jnp.take(pool, idx, axis=0), crop, flip,
                                  image_size, out_dtype)

        self._assemble = jax.jit(_assemble)

        def _update(pool, rows, start):
            return jax.lax.dynamic_update_slice(pool, rows, (start, 0, 0, 0))

        self._update = jax.jit(_update, donate_argnums=(0,))

    @property
    def pool_rows(self) -> int:
        return self._rows

    def _tick_refresh(self):
        """Apply last tick's (now-landed) transfer, then issue the next one.
        The device_put below is async: it has ``refresh_interval`` steps of
        compute to cross the link before _update consumes it."""
        import jax
        servable = self._loader.n_rows - \
            self._loader.n_rows % self._loader.batch_size
        if self._refresh_rows == 0 or servable <= self._rows:
            if servable <= self._rows and self._refresh_rows:
                # Every row the loader can serve is resident: nothing to
                # stream (the reference cache's fully-cached steady state).
                self._refresh_rows = 0
            return
        if self._pending is not None:
            rows_dev, labs, start = self._pending
            self._pool = self._update(self._pool, rows_dev, start)
            self._labels[start:start + len(labs)] = labs
            self._pending = None
        # Buffer whole loader batches and drain refresh_rows per tick: the
        # loader's batch size is the TRAINING batch (often > refresh_rows),
        # and dropping its surplus would amplify disk/gather work 4x at the
        # defaults.
        if self._buf_imgs is None or len(self._buf_imgs) < self._refresh_rows:
            raw = self._loader.next()
            if self._buf_imgs is None or not len(self._buf_imgs):
                self._buf_imgs, self._buf_labs = raw["images"], raw["labels"]
            else:
                self._buf_imgs = np.concatenate([self._buf_imgs, raw["images"]])
                self._buf_labs = np.concatenate([self._buf_labs, raw["labels"]])
        n = min(self._refresh_rows, len(self._buf_imgs),
                self._rows - self._cursor)
        rows_dev = jax.device_put(np.ascontiguousarray(self._buf_imgs[:n]))
        self._pending = (rows_dev, self._buf_labs[:n].astype(np.int32),
                         self._cursor)
        self._buf_imgs = self._buf_imgs[n:]
        self._buf_labs = self._buf_labs[n:]
        self._cursor += n
        if self._cursor >= self._rows:
            self._cursor = 0

    def next_batch(self, batch_size: int) -> Dict[str, np.ndarray]:
        """Assemble one on-device batch: ``{"images": [B,S,S,3] device array,
        "labels": [B] int32}`` — ready for the plain classification loss."""
        if self._step % self._refresh_interval == 0:
            self._tick_refresh()
        self._step += 1
        idx = self._rng.integers(0, self._rows, size=batch_size,
                                 dtype=np.int32)
        margin = self.record_size - self.image_size
        if self.train:
            crop = self._rng.integers(0, margin + 1, size=(batch_size, 2),
                                      dtype=np.int32)
            flip = self._rng.random(batch_size) < 0.5
        else:
            crop = np.full((batch_size, 2), margin // 2, np.int32)
            flip = np.zeros(batch_size, bool)
        images = self._assemble(self._pool, idx, crop, flip)
        return {"images": images, "labels": self._labels[idx]}


class AugmentingBatcher:
    """Adds per-example crop offsets and flip bits to raw record batches.

    ``train=True`` draws uniform crops + 50% flips (seeded, deterministic
    given the loader's batch order); ``train=False`` fixes the center crop
    and no flip — the reference's eval preprocessing. The heavy pixel work
    stays on device; this only draws ``[B, 2]`` + ``[B]`` small arrays.
    """

    def __init__(self, loader, image_size: int, record_size: int,
                 train: bool = True, seed: int = 0):
        if image_size > record_size:
            raise ValueError(f"image_size {image_size} exceeds record_size "
                             f"{record_size}")
        self._loader = loader
        self.image_size = image_size
        self.record_size = record_size
        self.train = train
        self._rng = np.random.Generator(np.random.PCG64(seed))

    def next(self) -> Dict[str, np.ndarray]:
        raw = self._loader.next()
        b = len(raw["images"])
        margin = self.record_size - self.image_size
        if self.train:
            crop = self._rng.integers(0, margin + 1, size=(b, 2), dtype=np.int32)
            flip = self._rng.random(b) < 0.5
        else:
            crop = np.full((b, 2), margin // 2, np.int32)
            flip = np.zeros(b, bool)
        return {"images": raw["images"], "labels": raw["labels"].astype(np.int32),
                "crop_yx": crop, "flip": flip}

    def __iter__(self) -> Iterator[Dict[str, np.ndarray]]:
        while True:
            yield self.next()
