"""``camera_stream``: a fixed camera's frames arriving at the ingest-time
index. The corpus is a run of scenes, each held for a number of frames
drawn uniformly from ``scene_frames`` (inclusive) from the seed; a
scene's first frame is the corpus's distinct frame of the scene's index
(``bench/data``), and every held repeat is that frame with independent
per-pixel sensor jitter of -1, 0 or +1 in 256 per channel, clipped to
the uint8 range (the jitter of ``repro.data.synthetic.
make_camera_stream``, copied here). The stream feeds the corpus in
order, cycled, ``feed_rows`` at a time, closed loop, as
``ingest_stream`` does; the skip detector aliases the held repeats, so
only about one frame a scene is scored.

``camera_frames`` makes the frame of any corpus row from the seed; the
feed and the reference both read it, so the reference sees the frames
the program got, bit for bit. Cuts are fitted on the configuration's
distinct calibration frames, as in ``ingest_stream``. The run's record
adds ``chunks``, the chunks the window scored on the device
(``IngestStats.chunks``), beside the frames it scored (``refs``).
"""
from __future__ import annotations

import numpy as np

from bench import data, loads, reference
from bench.loads import CLOCK, log

JITTER_TAG = 0xCA3


def scene_starts(seed: int, rows: int, lo: int, hi: int) -> np.ndarray:
    """First rows of the scenes that cover rows 0..rows-1."""
    rng = np.random.default_rng(seed & (2**64 - 1))
    starts = [0]
    while starts[-1] < rows:
        starts.append(starts[-1] + int(rng.integers(lo, hi + 1)))
    return np.asarray(starts[:-1], np.int64)


_JIT: dict = {}


def _jittered(key, u8, rows, held, hw: int):
    import jax
    import jax.numpy as jnp

    def one(row, img, h):
        noise = jax.random.randint(jax.random.fold_in(key, row),
                                   (hw, hw, 3), -1, 2)
        out = jnp.clip(img.astype(jnp.int32) + noise, 0, 255)
        return jnp.where(h, out, img.astype(jnp.int32)).astype(jnp.uint8)

    imgs = u8.reshape(len(rows), hw, hw, 3)
    return jax.vmap(one)(rows, imgs, held).reshape(-1)


def camera_frames(seed: int, rows: np.ndarray, hw: int,
                  scene_frames=(16, 64), *, as_uint8: bool = False):
    """Corpus rows ``rows`` of the camera stream of ``seed`` on the device:
    float32 (k / 256), or the flat uint8 values with ``as_uint8``."""
    import jax
    import jax.numpy as jnp

    rows = np.asarray(rows, np.int64)
    starts = scene_starts(seed, int(rows.max()) + 1, *scene_frames)
    scene = np.searchsorted(starts, rows, side="right") - 1
    held = rows != starts[scene]
    key = data.data_key(seed)
    u8 = data.frame_block(key, jnp.asarray(scene, jnp.int32), hw)
    if len(rows) not in _JIT:
        _JIT[len(rows)] = jax.jit(_jittered, static_argnums=4)
    u8 = _JIT[len(rows)](jax.random.fold_in(key, JITTER_TAG), u8,
                         jnp.asarray(rows, jnp.int32), jnp.asarray(held), hw)
    if as_uint8:
        return u8
    return (u8.astype(jnp.float32) * (1.0 / 256.0)).reshape(
        len(rows), hw, hw, 3)


class Load(loads.IngestStream):
    """A fixed camera: long held scenes, each repeat near-identical."""

    def __init__(self, config: dict, traffic: dict, seed: int):
        super().__init__(config, traffic, seed)
        self.scene_frames = tuple(traffic["scene_frames"])

    def _frames_of(self, seed: int):
        def get(lo, hi):
            rows = np.arange(lo, lo + self.block)
            rows[hi - lo:] = hi - 1
            return camera_frames(seed, rows, self.hw, self.scene_frames)
        return get

    def _host_frames(self) -> np.ndarray:
        out = np.empty((self.n, self.hw, self.hw, 3), np.float32)
        step = 256
        for lo in range(0, self.n, step):
            hi = min(lo + step, self.n)
            rows = np.arange(lo, lo + step)
            rows[hi - lo:] = hi - 1
            u8 = np.asarray(camera_frames(self.seed, rows, self.hw,
                                          self.scene_frames, as_uint8=True))
            np.multiply(u8.reshape(step, self.hw, self.hw, 3)[:hi - lo],
                        np.float32(1.0 / 256.0), out=out[lo:hi])
        return out

    def setup(self, seconds: float) -> None:
        from repro.engine.ingest import IngestPipeline

        t = CLOCK()
        self.frames = self._host_frames()
        scenes = len(scene_starts(self.seed, self.n, *self.scene_frames))
        log(f"frames: {self.n} x {self.hw}px, {scenes} scenes, made in "
            f"{CLOCK() - t:.2f} s")
        t = CLOCK()
        wseed = int(self.config["weights_seed"])
        self.params = data.make_weights(wseed, self.preds)
        fit = self.config["cuts"]
        # the configuration's cuts: fitted on its distinct frames
        calib, _ = reference.score_frames(
            loads.IngestStream._frames_of(self, wseed),
            int(fit["calibration_rows"]), self.block, self.preds,
            self.params, "highest", int(self.pipe["skip_res"]))
        self.cuts = [data.quantile_cuts(s, fit["low_quantile"],
                                        fit["high_quantile"])
                     for s in calib]
        self.cascades = [data.build_cascade(i, p, self.params[i], c)
                         for i, (p, c) in enumerate(zip(self.preds,
                                                        self.cuts))]
        log(f"weights and cuts: {CLOCK() - t:.2f} s")
        p = self.pipe
        self.capacity = int(p["capacity_rows"])
        self.pipeline = IngestPipeline(
            self.cascades, self.capacity, chunk=int(p["chunk"]),
            skip=bool(p["skip"]), skip_threshold=float(p["skip_threshold"]),
            skip_res=int(p["skip_res"]), top_k=p["top_k"],
            prune_margin=float(p["prune_margin"]), int8=False)
        self.fed = 0
        t = CLOCK()
        with self.spans("warmup"):
            self._feed()
        log(f"warm-up: {self.fed} frames in {CLOCK() - t:.2f} s")

    def window(self, seconds: float) -> dict:
        chunks = self.pipeline.stats.chunks
        out = super().window(seconds)
        self.chunks_in = self.pipeline.stats.chunks - chunks
        return out

    def record(self) -> dict:
        return dict(super().record(), chunks=self.chunks_in)
