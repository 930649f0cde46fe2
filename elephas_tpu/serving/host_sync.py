"""The serving hot path's ONE sanctioned device→host synchronization
point.

Every device→host read in ``elephas_tpu.serving`` funnels through this
module so the pipelining contract is auditable: the scheduler dispatches
decode step N+1 BEFORE it reads step N's tokens back, and the only
place a read can block is here. ``scripts/lint_blocking.py`` (wired
into tier-1) statically rejects any other blocking conversion
(``int(``/``float(``/``.item()``/``np.asarray``/``device_get``/
``block_until_ready``) inside the serving package, so a future edit
cannot quietly reintroduce a per-token sync.

Two facts about a device's in-order execution stream (re-measured on
JAX 0.9.0's CPU backend in PR 22; not measured on the TPU) dictate the
shape of ``fetch_lanes``:

- fetching program N's OUTPUT buffer does NOT wait on program N+1
  dispatched after it — the transfer only waits for N's completion
  event, which is what makes one-step lookahead overlap at all;
- an eagerly-dispatched device GATHER of the active lanes is a new
  program and queues BEHIND the in-flight decode, serializing the
  pipeline (measured: a 2-lane take() blocked for the full decode).

So "fetch only the active lanes" means: one device_get of the whole
(max_slots,) token buffer — a handful of bytes — then converting ONLY
the active lanes on the host copy. The thing the satellite actually
bans is the old per-lane ``int(device_array[i])`` loop over all
``max_slots`` lanes, each a separate indexing program + blocking sync.
"""

from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import numpy as np


def fetch(value):
    """Blocking device→host transfer of ``value`` (array or pytree).

    THE sanctioned sync point for ``elephas_tpu.serving``. Returns
    numpy arrays (or a pytree of them). Callers convert lanes/scalars
    from the HOST copy — never from the device array.
    """
    return jax.device_get(value)


def fetch_scalar(value) -> int:
    """Fetch a device scalar as a python int (prefill's first token)."""
    return int(fetch(value))  # host-ok: sanctioned sync point


def fetch_lanes(tokens, lanes: Sequence[int]) -> List[Tuple[int, int]]:
    """Fetch ``tokens`` (a (max_slots,) device vector) and convert ONLY
    the ``lanes`` requested, as ``[(lane, token), ...]``.

    One bulk transfer + host-side lane selection; see the module
    docstring for why this beats both a device gather (serializes
    behind the in-flight decode) and the per-lane int() loop (one
    blocking sync per slot, active or not).
    """
    host = np.asarray(fetch(tokens))  # host-ok: sanctioned sync point
    return [(lane, int(host[lane])) for lane in lanes]  # host-ok: numpy


def _lists(rider):
    return jax.tree_util.tree_map(
        lambda a: np.asarray(a).tolist(), rider)  # host-ok: numpy


def fetch_lanes_and(tokens, lanes: Sequence[int], extra):
    """``fetch_lanes`` with a rider: ``extra`` (a device vector or a list
    of them: a step's counters) comes back in the SAME transfer as the
    lanes' tokens, as plain lists of floats. Returns ``(pairs, extra)``."""
    host, rider = fetch((tokens, extra))
    host = np.asarray(host)  # host-ok: sanctioned sync point
    return [(lane, int(host[lane])) for lane in lanes], _lists(rider)  # host-ok: numpy


def fetch_scalar_and(value, extra):
    """``fetch_scalar`` with the same rider."""
    host, rider = fetch((value, extra))
    return int(host), _lists(rider)  # host-ok: sanctioned sync point
