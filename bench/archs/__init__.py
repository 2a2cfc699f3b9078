"""One module per architecture: everything the harness knows of a model's
weights, equations, work and kernels.

A configuration file names its module with the required key
``"bench_arch"``: ``"qwen_dense"`` is ``bench/archs/qwen_dense.py``.  There
is no default; a file without the key fails at load.  The rest of
``bench/`` is independent of the architecture and reaches the module
through :func:`of`.  So a configuration of a new architecture enters by
adding its configuration file, its module here, its cell and traffic
files, and readers under ``bench/metrics/``; no other file changes.

A module provides:

- ``spec_of(conf) -> dict``: the sizes and switches it reads from the
  configuration file, by short name, with hashable values.  Two names
  are read outside the module: ``V`` (the vocabulary) and ``dtype`` (the
  served dtype's name).
- ``groups(s) -> list[(group, layers, layout)]``: the layer groups of the
  program's parameter tree, in the order their layers run.  ``layout``
  is ``"stacked"`` (each leaf stacked over the group's layers on axis 0,
  as ``blocks``) or ``"list"`` (a list of per-layer trees, as
  ``dense_blocks``).
- ``leaves(s) -> list[(name, group, shape, mean, stddev)]``: every weight,
  by its dotted path in the program's tree (``group.<path>`` for a layer
  leaf, whose ``shape`` is one layer's); ``group`` is None for a leaf
  outside the layers.  ``bench/weights.py`` draws each from
  ``(seed, name, index of the layer in its group)``.
- ``model_config(conf)``: the program's ``ModelConfig``.
- The plain reference's equations, in float32 (``bench/reference.py``
  runs them block by block, layer by layer):
  ``embed(s, g, tokens)`` -> hidden states, ``layer(s, group, w, x, mm)``
  -> one layer of ``group`` over one whole sequence ``x`` (S, d),
  ``final(s, g, h)`` -> the normed hidden states the head reads, and
  ``head(s, g)`` -> the (d, V) head matrix.  ``g`` holds the leaves
  outside the layers by name, ``w`` one layer's leaves by their path
  inside the group, both in the served dtype; ``mm(a, w)`` is the
  reference's matmul (float32 at ``HIGHEST``, or through fp8 for the
  control), used for every projection.  Any other contraction runs at
  :data:`HI`, and attention takes its queries :data:`QBLOCK` rows at a
  time (the runner pads every sequence to a multiple of it).
- ``model_flops(s, keys, sampled)``: forward FLOPs of tokens that attend
  ``keys`` keys each (an array, one entry per token), ``sampled`` of them
  through the LM head.
- ``KERNELS``: ``{kernel: Kernel}``, each with the substrings that name it
  among the device operations, the program class it marks (or None), and
  its least work for what a traced span served (a ``bench.work.Served``:
  the served lengths).  ``bench/trace_reduce.py`` reads the names and
  classes from this table as it stands.
"""
from __future__ import annotations

import importlib
from typing import Callable, NamedTuple

import jax

# the package :func:`of` looks in
PACKAGE = __name__

HI = jax.lax.Precision.HIGHEST   # the reference's precision
QBLOCK = 512                     # attention query rows per block


class Kernel(NamedTuple):
    names: tuple[str, ...]      # substrings naming it among device ops
    program: str | None         # the program class its presence marks
    work: Callable              # (spec, bench.work.Served) -> bench.work.Work


def of(conf: dict):
    """The module named by the configuration's ``bench_arch``."""
    name = conf["bench_arch"]
    if not name.isidentifier():
        raise ValueError(f"bench_arch {name!r} is not a module name")
    return importlib.import_module(f"{PACKAGE}.{name}")

