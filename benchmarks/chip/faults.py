"""Faults planted under the timed path, to show that ``correct`` sees
them.  ``readings.py`` plants one on the chip and the rehearsal test on
the CPU; the benchmark's own runs never do.

Each fault wraps ``VisionEngine._run_batch(images, bucket)``, which pads
the executed batch to its bucket, runs the forward and returns one logits
row per request, in the requests' order:

* ``swap_one_slot``: the first two requests of a batch get each other's
  rows;
* ``roll_batch``: every request gets its neighbour's row;
* ``leak_padding``: in a padded batch, the last request gets the row of a
  padding image (zeros) in place of its own;
* ``alter_rows``: every row is altered where it is produced, by noise of
  the row's own norm.
"""
from __future__ import annotations

import contextlib

import numpy as np


def swap_one_slot(run, images, bucket):
    rows = np.array(run(images, bucket))
    if len(rows) >= 2:
        rows[[0, 1]] = rows[[1, 0]]
    return rows


def roll_batch(run, images, bucket):
    return np.roll(run(images, bucket), 1, axis=0)


def leak_padding(run, images, bucket):
    if len(images) < bucket:
        images = np.array(images)
        images[-1] = 0.0
    return run(images, bucket)


def alter_rows(run, images, bucket):
    rows = np.asarray(run(images, bucket))
    noise = np.random.default_rng(0).standard_normal(rows.shape)
    return rows + np.linalg.norm(rows, axis=1, keepdims=True) \
        * noise / np.sqrt(rows.shape[1])


FAULTS = {f.__name__: f for f in (swap_one_slot, roll_batch, leak_padding,
                                  alter_rows)}


@contextlib.contextmanager
def planted(name: str):
    """``VisionEngine._run_batch`` broken by the fault ``name`` while the
    context is open."""
    from repro.serving.vision import VisionEngine
    fault, run_batch = FAULTS[name], VisionEngine._run_batch

    def broken(self, images, bucket):
        return fault(lambda im, b: run_batch(self, im, b), images, bucket)
    VisionEngine._run_batch = broken
    try:
        yield
    finally:
        VisionEngine._run_batch = run_batch
