"""Ragged all-to-all exchange for expert-parallel dispatch.

On TPU this is `jax.lax.ragged_all_to_all` — the ICI collective that
moves each shard's variable-size per-peer chunks without capacity
padding (the TPU-native answer to the reference stack's NCCL
all-to-all in vLLM's expert parallelism; SURVEY §2.4 EP row).

XLA:CPU has no lowering for the primitive ("HLO opcode
`ragged-all-to-all` is not supported by XLA:CPU ThunkEmitter"), so the
virtual-mesh tests and the driver's CPU dryrun run a semantics-exact
emulation built from all_gather + masked scatter. Same interface, same
offsets contract, chosen by ONE explicit switch:
``RAY_TPU_RAGGED_EMULATE=1``, which the CPU-mesh recipe
(`_private/cpu_mesh.apply_cpu_mesh_env`) sets. Without it the op is the
native collective on whatever backend runs — a backend that lacks it
raises; nothing falls back by looking at the platform.

Semantics (mirrors lax.ragged_all_to_all): for each peer j, rows
``operand[input_offsets[j] : input_offsets[j] + send_sizes[j]]`` land in
peer j's ``output`` at row ``output_offsets[j]`` (the offset in the
RECEIVER's buffer, known to the sender); ``recv_sizes[j]`` is how many
rows this shard receives from peer j. Rows of ``output`` not written to
keep their initial values.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
from jax import lax


def exchange_offsets(send_sizes: jax.Array, axis_name: str):
    """Derive (input_offsets, output_offsets, recv_sizes) from per-peer
    send_sizes: one all_to_all of the counts, one of the receiver-side
    exclusive cumsums (each peer must learn where ITS chunk starts in
    the receiver's buffer)."""
    recv_sizes = lax.all_to_all(send_sizes, axis_name, 0, 0)
    off_in_recv = jnp.cumsum(recv_sizes) - recv_sizes
    output_offsets = lax.all_to_all(off_in_recv, axis_name, 0, 0)
    input_offsets = jnp.cumsum(send_sizes) - send_sizes
    return input_offsets, output_offsets, recv_sizes


def _use_native() -> bool:
    return os.environ.get("RAY_TPU_RAGGED_EMULATE", "0") not in (
        "1", "true")


def ragged_all_to_all(operand: jax.Array, output: jax.Array,
                      input_offsets: jax.Array, send_sizes: jax.Array,
                      output_offsets: jax.Array, recv_sizes: jax.Array,
                      *, axis_name: str) -> jax.Array:
    """Call inside shard_map over ``axis_name``. operand/output are 2-D
    ``[rows, features]`` per-shard buffers."""
    if _use_native():
        return lax.ragged_all_to_all(
            operand, output, input_offsets, send_sizes,
            output_offsets, recv_sizes, axis_name=axis_name)
    return _emulated(operand, output, input_offsets, send_sizes,
                     output_offsets, recv_sizes, axis_name=axis_name)


def _emulated(operand, output, input_offsets, send_sizes,
              output_offsets, recv_sizes, *, axis_name):
    del recv_sizes   # receiver layout is fully determined by the senders
    rows, _ = operand.shape
    row = jnp.arange(rows)
    # classify each operand row: destination peer + position in chunk
    inrange = ((row[None, :] >= input_offsets[:, None])
               & (row[None, :] < (input_offsets + send_sizes)[:, None]))
    dest = jnp.argmax(inrange, axis=0)               # [rows]
    valid = inrange.any(axis=0)
    pos = row - input_offsets[dest]
    me = lax.axis_index(axis_name)
    ops = lax.all_gather(operand, axis_name)          # [P, rows, H]
    dests = lax.all_gather(dest, axis_name)           # [P, rows]
    poss = lax.all_gather(pos, axis_name)
    valids = lax.all_gather(valid, axis_name)
    outoffs = lax.all_gather(output_offsets, axis_name)   # [P_sender, P]
    # sender s's chunk for me starts at outoffs[s, me]
    tgt = outoffs[:, me][:, None] + poss              # [P, rows]
    tgt = jnp.where((dests == me) & valids, tgt, output.shape[0])
    flat = ops.reshape(-1, operand.shape[1])
    return output.at[tgt.reshape(-1)].set(flat, mode="drop")
