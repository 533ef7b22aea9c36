"""The comparison that decides ``correct`` for a served model.

For each sampled request the reference runs once over the prompt followed
by the served tokens. At every position that produced a served token, the
gap is the reference's best logit minus its logit of the served token:
0 where the program chose the reference's argmax, small where rounding
flipped a near tie. The number compared is the widest gap over the sample.

The control puts the reference computed in fp8 in the program's place: at
the same positions it reads the gap, in the float32 reference, of the
token the fp8 reference puts first.
"""
from __future__ import annotations

from typing import List, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

Item = Tuple[np.ndarray, Sequence[int]]      # (prompt, served tokens)


def pack(items: Sequence[Item], length: int):
    """Token rows padded to ``length``, the served token due at each
    position, and which positions produced one."""
    toks = np.zeros((len(items), length), np.int32)
    tgt = np.zeros((len(items), length), np.int32)
    mask = np.zeros((len(items), length), bool)
    for i, (prompt, served) in enumerate(items):
        p, n = len(prompt), len(served)
        seq = np.concatenate([prompt, np.asarray(served[:-1], np.int32)])
        if len(seq) > length:
            raise ValueError(f"request of {len(seq)} tokens > {length}")
        toks[i, :len(seq)] = seq
        tgt[i, p - 1:p - 1 + n] = served
        mask[i, p - 1:p - 1 + n] = True
    return toks, tgt, mask


def _batches(items: List[Item], size: int):
    for i in range(0, len(items), size):
        yield items[i:i + size]


class Comparator:
    """Jitted reference passes at one padded length; one compile each."""

    def __init__(self, ref, config: dict, length: int, batch: int):
        self.length, self.batch = length, batch

        def gap(params, toks, tgt, mask):
            lg = ref.logits(params, toks, config)
            best = jnp.max(lg, axis=-1)
            got = jnp.take_along_axis(lg, tgt[..., None], axis=-1)[..., 0]
            return jnp.where(mask, best - got, -jnp.inf)

        def low_argmax(params, toks):
            return jnp.argmax(ref.logits(params, toks, config, low=True), axis=-1)

        self._gap = jax.jit(gap)
        self._low_argmax = jax.jit(low_argmax)

    def _run(self, params, items: List[Item], control: bool):
        widest, n = 0.0, 0
        for chunk in _batches(items, self.batch):
            pad = chunk + [chunk[-1]] * (self.batch - len(chunk))
            toks, tgt, mask = pack(pad, self.length)
            mask[len(chunk):] = False
            if control:
                tgt = np.asarray(self._low_argmax(params, jnp.asarray(toks)))
            g = np.asarray(self._gap(params, jnp.asarray(toks),
                                     jnp.asarray(tgt), jnp.asarray(mask)))
            widest = max(widest, float(g.max()))
            n += int(mask.sum())
        return widest, n

    def widest_gap(self, params, items: List[Item]):
        """(widest gap of the served tokens, tokens compared)."""
        return self._run(params, items, control=False)

    def control_gap(self, params, items: List[Item]):
        """(widest gap of the fp8 reference's tokens, tokens compared)."""
        return self._run(params, items, control=True)


def sample(done: List[dict], seed: int, min_tokens: int) -> List[dict]:
    """Requests drawn from the seed, the longest first, until they hold
    ``min_tokens`` served tokens."""
    if not done:
        return []
    order = sorted(done, key=lambda r: -(len(r["prompt"]) + len(r["tokens"])))
    rest = order[1:]
    np.random.default_rng(seed).shuffle(rest)
    out, n = [], 0
    for r in [order[0]] + rest:
        out.append(r)
        n += len(r["tokens"])
        if n >= min_tokens:
            break
    return out
