"""ctypes bindings for the native prefetching batch loader.

Builds ``native/loader.cpp`` into a shared library on first use (cached
under ``native/build/``, named by the source's content hash so a library
built from other source is never loaded) and exposes :class:`PrefetchLoader`, an iterator of
shuffled (data, labels) batches assembled by a background C++ thread — host
input work overlaps device compute. Without a C++ toolchain it raises
``NativeLoaderUnavailable``; a caller that asked for the native loader
gets that error, not another loader (``examples.data.batches`` is the
python one to ask for instead).
"""

from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess
import threading

import numpy as np

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
_SRC = os.path.join(_REPO_ROOT, 'native', 'loader.cpp')
_BUILD_DIR = os.path.join(_REPO_ROOT, 'native', 'build')

_lib = None
_lib_lock = threading.Lock()


class NativeLoaderUnavailable(RuntimeError):
    pass


def _load_lib() -> ctypes.CDLL:
    global _lib
    with _lib_lock:
        if _lib is not None:
            return _lib
        if not os.path.exists(_SRC):
            raise NativeLoaderUnavailable(f'missing source {_SRC}')
        # keyed by content, not mtime: a copied or checked-out tree says
        # nothing true about which file is newer
        with open(_SRC, 'rb') as f:
            digest = hashlib.sha256(f.read()).hexdigest()[:16]
        so = os.path.join(_BUILD_DIR, f'libkfacloader-{digest}.so')
        if not os.path.exists(so):
            os.makedirs(_BUILD_DIR, exist_ok=True)
            tmp = f'{so}.tmp.{os.getpid()}'
            cmd = [
                'g++', '-O2', '-shared', '-fPIC', '-std=c++17', '-pthread',
                _SRC, '-o', tmp,
            ]
            try:
                subprocess.run(cmd, check=True, capture_output=True)
            except (OSError, subprocess.CalledProcessError) as e:
                raise NativeLoaderUnavailable(f'build failed: {e}') from e
            os.replace(tmp, so)  # atomic: racing builders both succeed
        lib = ctypes.CDLL(so)
        lib.loader_create.restype = ctypes.c_void_p
        lib.loader_create.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
        ]
        lib.loader_create_aug.restype = ctypes.c_void_p
        lib.loader_create_aug.argtypes = [
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_int64,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_void_p, ctypes.c_void_p, ctypes.c_uint64, ctypes.c_int,
            ctypes.c_int64, ctypes.c_int64, ctypes.c_int64, ctypes.c_int64,
            ctypes.c_int, ctypes.c_int64,
        ]
        lib.loader_next.restype = ctypes.c_int64
        lib.loader_next.argtypes = [ctypes.c_void_p, ctypes.POINTER(ctypes.c_int64)]
        lib.loader_release.argtypes = [ctypes.c_void_p, ctypes.c_int64]
        lib.loader_batches_per_epoch.restype = ctypes.c_int64
        lib.loader_batches_per_epoch.argtypes = [ctypes.c_void_p]
        lib.loader_destroy.argtypes = [ctypes.c_void_p]
        _lib = lib
        return lib


class PrefetchLoader:
    """Iterate shuffled batches assembled by the native worker thread.

    Args:
        data: (n, ...) float32 array. May be memory-mapped (e.g.
            ``np.load(..., mmap_mode='r')``): if it is already C-contiguous
            float32, no copy is made and the C++ worker reads the mapped
            pages directly — the on-disk ImageNet-style layout.
        labels: (n,) int32 array.
        batch_size: samples per batch.
        n_ring: prefetch depth (ring buffer slots).
        seed: shuffle seed.
        drop_last: drop the final ragged batch each epoch.
        augment: optional dict enabling in-worker image augmentation for
            (H, W, C) samples: ``{'pad': 4, 'flip': True}`` applies the
            reference CIFAR pipeline (RandomCrop(padding=pad) +
            RandomHorizontalFlip, examples/vision/datasets.py) on the host
            thread, overlapped with device compute.
    """

    def __init__(
        self,
        data: np.ndarray,
        labels: np.ndarray,
        batch_size: int,
        n_ring: int = 3,
        seed: int = 0,
        drop_last: bool = True,
        augment: dict | None = None,
        start_epoch: int = 0,
    ) -> None:
        lib = _load_lib()
        self._lib = lib
        self.data = np.ascontiguousarray(data, dtype=np.float32)
        self.labels = np.ascontiguousarray(labels, dtype=np.int32)
        n = len(self.data)
        if drop_last and n < batch_size:
            raise ValueError(
                f'{n} samples yield zero batches of size {batch_size} with '
                'drop_last=True'
            )
        self.sample_shape = self.data.shape[1:]
        sample_elems = int(np.prod(self.sample_shape)) if self.sample_shape else 1
        self.batch_size = batch_size
        self._ring_data = np.empty(
            (n_ring, batch_size, sample_elems), dtype=np.float32
        )
        self._ring_labels = np.empty((n_ring, batch_size), dtype=np.int32)
        if augment is not None and len(self.sample_shape) != 3:
            raise ValueError(
                f'augment needs (H, W, C) samples, got {self.sample_shape}'
            )
        h, w, c = self.sample_shape if augment is not None else (0, 0, 0)
        self._handle = lib.loader_create_aug(
            self.data.ctypes.data_as(ctypes.c_void_p),
            self.labels.ctypes.data_as(ctypes.c_void_p),
            n, sample_elems, batch_size, n_ring,
            self._ring_data.ctypes.data_as(ctypes.c_void_p),
            self._ring_labels.ctypes.data_as(ctypes.c_void_p),
            seed, int(drop_last),
            h, w, c,
            int(augment.get('pad', 4)) if augment is not None else 0,
            int(bool(augment.get('flip', True))) if augment is not None else 0,
            int(start_epoch),
        )
        self.batches_per_epoch = int(lib.loader_batches_per_epoch(self._handle))
        # epoch the next epoch_batches() call serves (start_epoch on resume)
        self._next_epoch = int(start_epoch)

    def __iter__(self):
        return self.epoch_batches()

    def epoch_batches(self):
        """Yield one epoch of (data, labels) batches (copies — safe to hold).

        The producer free-runs across epochs; if a previous consumer stopped
        early (break/exception), slots from the unfinished epoch are drained
        here using the producer's epoch counter, so every call starts at a
        fresh epoch boundary — no keep-consuming contract on the caller.
        """
        target = self._next_epoch
        self._next_epoch = target + 1
        yielded = 0
        while yielded < self.batches_per_epoch:
            epoch = ctypes.c_int64()
            slot = self._lib.loader_next(self._handle, ctypes.byref(epoch))
            if slot < 0:
                return
            if epoch.value < target:  # leftover from an abandoned epoch
                self._lib.loader_release(self._handle, slot)
                continue
            x = self._ring_data[slot].reshape(
                (self.batch_size,) + self.sample_shape
            ).copy()
            y = self._ring_labels[slot].copy()
            self._lib.loader_release(self._handle, slot)
            yield x, y
            yielded += 1

    def close(self) -> None:
        if self._handle is not None:
            self._lib.loader_destroy(self._handle)
            self._handle = None

    def __del__(self):  # pragma: no cover - best effort
        try:
            self.close()
        except Exception:
            pass
