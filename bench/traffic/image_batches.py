"""The one generator of image-classification traffic; a mix file names it
by ``"kind": "image_batches"``.

A mix holds ``batch`` (images per request) and ``pool`` (distinct batches
made in set-up and sent in turn).  Batch ``i`` of a run is pool entry
``i % pool`` with its own comparator key, so every batch the window sends
is fresh work for the spiking layers while set-up stays short.

The images are the repository's procedural gratings (10 classes of
oriented sinusoids with phase, amplitude, colour and noise jitter), a
copy of ``repro.data.synthetic_images.sample_batch`` so that the yardstick
does not move with the program.
"""

from __future__ import annotations

from typing import Dict

import jax
import jax.numpy as jnp


def sample_images(key, batch: int, size: int, channels: int = 3,
                  num_classes: int = 10, noise: float = 0.35):
    """[batch, size, size, channels] float32 images in [0, 1]."""
    k_cls, k_phase, k_amp, k_noise, k_col = jax.random.split(key, 5)
    labels = jax.random.randint(k_cls, (batch,), 0, num_classes)
    angles = jnp.linspace(0.0, jnp.pi * 0.9, num_classes)
    freqs = 2.0 + 3.0 * (jnp.arange(num_classes) % 3)
    a, f = angles[labels], freqs[labels]
    phase = jax.random.uniform(k_phase, (batch,)) * 2 * jnp.pi
    amp = 0.7 + 0.3 * jax.random.uniform(k_amp, (batch,))
    xs = jnp.linspace(0, 1, size)
    gx, gy = jnp.meshgrid(xs, xs, indexing="ij")
    arg = (2 * jnp.pi * f[:, None, None]
           * (gx[None] * jnp.cos(a)[:, None, None]
              + gy[None] * jnp.sin(a)[:, None, None])
           + phase[:, None, None])
    base = amp[:, None, None] * jnp.sin(arg)
    col = 0.5 + 0.5 * jax.random.uniform(k_col, (batch, 1, 1, channels))
    img = base[..., None] * col
    img = img + noise * jax.random.normal(k_noise, img.shape)
    return jnp.clip(0.5 * (img + 1.0), 0.0, 1.0).astype(jnp.float32)


class ImageTraffic:
    def __init__(self, mix: Dict, key, image_size: int, channels: int,
                 num_classes: int):
        self.batch = int(mix["batch"])
        self.pool = int(mix["pool"])
        k_img, self._k_rng = jax.random.split(key)
        make = jax.jit(lambda k: jax.vmap(
            lambda kk: sample_images(kk, self.batch, image_size, channels,
                                     num_classes))(
            jax.random.split(k, self.pool)))
        self.images = make(k_img)  # [pool, batch, H, W, C], on the device

    def batch_images(self, i: int):
        return self.images[i % self.pool]

    def batch_key(self, i: int):
        """The comparator/coding key of batch ``i`` (the forward's rng)."""
        return jax.random.fold_in(self._k_rng, i)


def make(mix: Dict, key, image_size: int, channels: int, num_classes: int):
    return ImageTraffic(mix, key, image_size, channels, num_classes)
