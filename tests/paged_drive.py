"""The engine's serving programs driven by hand, as the server's join and
chunk drive them: a pool in which every slot owns a whole chain, and a
prompt prefilled into one slot's chain."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np


def alloc_chains(eng, num_slots: int, block_size: int = 8):
    """A fresh pool whose slot ``b`` owns blocks ``1 + b*mb .. (b+1)*mb``
    (block 0 is the null block)."""
    mb = -(-eng.max_len // block_size)
    paged = eng.alloc_paged(
        num_slots, block_size=block_size, num_blocks=num_slots * mb + 1
    )
    tables = 1 + np.arange(num_slots * mb, dtype=np.int32).reshape(num_slots, mb)
    return dataclasses.replace(paged, tables=jnp.asarray(tables))


def join(eng, paged, slot: int, ids):
    """Prefill ``ids`` into ``slot``'s chain in one chunk and scatter it
    into the pool. Returns ``(token0, paged')``."""
    p_len = len(ids)
    kbuf, vbuf = eng.paged_kbuf_zeros(p_len)
    logits, kbuf, vbuf = eng.prefill_chunk(
        kbuf, vbuf, jnp.asarray([ids], jnp.int32), 0, p_len - 1
    )
    paged = eng.complete_paged_prefill(paged, kbuf, vbuf, paged.tables[slot], 0)
    paged = dataclasses.replace(paged, lengths=paged.lengths.at[slot].set(p_len))
    return int(eng.sample_logits(logits, jax.random.PRNGKey(0))[0]), paged
