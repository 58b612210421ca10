"""Random Order Coding (ROC) — bits-back coding of id *sets*.

This is the paper's primary codec (Section 3.2 / 4.2).  A cluster's id list
is order-invariant, so a sequence of ``n`` unique ids drawn from ``[N)``
carries ``log n!`` fewer bits than its naive encoding.  ROC collects exactly
that saving with an ANS stack:

encode (per cluster, ids need not be pre-sorted)::

    for i = n .. 1:                       # i = number of ids remaining
        j   = ans.pop_uniform(i)          # bits-back: sample a rank (-log i bits)
        x   = j-th smallest remaining id  # order statistics (Fenwick)
        ans.push_uniform(x, N)            # id model: uniform over [N)  (+log N bits)

decode::

    for i = 1 .. n:
        x = ans.pop_uniform(N)
        j = rank of x among ids decoded so far (after insertion)
        ans.push_uniform(j, i)            # return the borrowed bits

Both loops are exact mirrors, so the ANS state round-trips exactly; with the
exact big-integer coder (``BigANS``) the rate is ``log2 C(N, n)`` up to +1
bit, with **no initial-bits overhead**: starting from state 0, early
``pop_uniform`` calls on a small state are still bijective (they return
low-entropy ranks), which is the cleanest resolution of the paper's
"initial bits issue" for the offline/online settings alike.

Split decode.  Each decode step is ``s <- (s // N) * i + j_i`` with
``x_i = s % N``, so the loop above touches the whole state, about
``log2 C(N, n)`` bits, twice per id.  Write ``s = H * N**m + L``
(``divmod``).  While the ``H`` term is still a multiple of ``N``, which
holds for ``m`` steps (step ``t`` turns it into ``H * i_1..i_t * N**(m-t)``),
each ``x_t`` is the current ``L % N`` and ``L`` steps alone:
``L <- (L // N) * i_t + j_t``.  After the ``m`` steps the state is exactly
``H * i_1*..*i_m + L``.  ``roc_pop_set`` applies this recursively: split off
half of the remaining count, decode it from its low part (recursing), put
the state back together with one multiply, decode the rest the same way.
Parts of at most ``SPLIT_LEAF_IDS`` ids run the per-id loop on an integer of
a few dozen machine words; the full-width state is touched ``O(log n)``
times per list instead of ``2n``.  The ids and the state after are those of
the per-id loop, bit for bit, so joint streams pop on from the same place.

The leaf size comes from timings of one list decode on an Intel Xeon core
under CPython 3.12 (universe ``10**6``, median of 9 lists, best of 5 runs,
in us; "before" is the loop through ``BigANS.pop_uniform``/``push_uniform``,
"loop" the same steps inline without the split)::

    n       before   loop   leaf 16   leaf 32   leaf 48   leaf 64   leaf 96
    64          75     42        50        44        42        43        40
    128        193    105       108        98       100       105       103
    192        325    190       181       166       162       165       181
    362        665    381       309       266       264       253       295
    909       3870   2400      1603      1427      1509      1544      1503
    2406     20532  12683      6634      6198      6092      6068      6192

Every length takes the split, so there is one path.  A list of
``SPLIT_LEAF_IDS`` ids or fewer (graph friend lists, small epochs) splits
once, ``divmod(s, N**n)``: on its own blob that is within noise of the
loop (16-128 ids: 8.4 against 7.8 us at 16, 11.9 against 11.9 at 32, 110
against 111 at 128), and on a joint stream, where ``s`` holds every list
pushed before it, it spares the loop's two full-width operations an id
(the last of 200 lists of 16 ids: 81 against 314 us; of 127 ids: 2.8
against 15.2 ms).

Differences from the paper's C++ implementation (documented in DESIGN.md):
the paper uses a fixed-width streaming ANS where the initial state is filled
with random bits; we use the exact coder for rate reporting (the paper notes
ANS redundancy is ~2e-5 bits/op — unobservable at our scales) and the
vectorized lane coder (``repro.core.gap_ans``) for the TPU-adapted fast path.
"""

from __future__ import annotations

import bisect
import math
from typing import List, Sequence

import numpy as np

from .ans import BigANS
from .fenwick import Fenwick

__all__ = [
    "roc_push_set",
    "roc_pop_set",
    "roc_encode_clusters",
    "roc_decode_clusters",
    "set_information_bits",
]

# The split decode runs the per-id loop on this many ids or fewer at once.
# Set from the CPU timings in the module docstring.
SPLIT_LEAF_IDS = 48


def roc_push_set(ans: BigANS, ids: Sequence[int], alphabet: int) -> None:
    """Push the *set* of unique ``ids`` (subset of ``[alphabet)``) onto ``ans``."""
    sorted_ids = np.sort(np.asarray(ids, dtype=np.int64))
    n = int(sorted_ids.size)
    if n == 0:
        return
    if sorted_ids[0] < 0 or sorted_ids[-1] >= alphabet:
        raise ValueError("ids out of range")
    if n > 1 and np.any(sorted_ids[1:] == sorted_ids[:-1]):
        raise ValueError("ROC set codec requires unique ids")
    ids_list = [int(v) for v in sorted_ids]
    if n <= 512:
        # O(n^2) memmove path: faster than Fenwick for small clusters.
        for i in range(n, 0, -1):
            j = ans.pop_uniform(i)
            x = ids_list.pop(j)
            ans.push_uniform(x, alphabet)
    else:
        fw = Fenwick.ones(n)
        for i in range(n, 0, -1):
            j = ans.pop_uniform(i)
            pos = fw.find(j)
            fw.add(pos, -1)
            ans.push_uniform(ids_list[pos], alphabet)


def roc_pop_set(ans: BigANS, n: int, alphabet: int) -> np.ndarray:
    """Pop a set of ``n`` ids; returns them sorted ascending.

    Split decode (module docstring): the ids, and ``ans.state`` after, are
    those of the per-id loop.
    """
    out: List[int] = []
    ans.state = _pop_split(ans.state, out, 1, int(n), int(alphabet))
    return np.asarray(out, dtype=np.int64)


def _pop_loop(s: int, out: List[int], a: int, cnt: int, alphabet: int) -> int:
    """Decode ``cnt`` ids, counters ``i = a .. a+cnt-1``, one per step of
    ``s``; inserts each into the sorted ``out`` and returns the state after."""
    insert, rank = out.insert, bisect.bisect_left
    for i in range(a, a + cnt):
        s, x = divmod(s, alphabet)           # pop_uniform(alphabet)
        j = rank(out, x)
        insert(j, x)
        s = s * i + j                        # push_uniform(j, i)
    return s


def _pop_split(s: int, out: List[int], a: int, cnt: int, alphabet: int) -> int:
    """:func:`_pop_loop`'s result, with the per-id steps run on low parts of
    ``s`` split off by ``divmod`` (see the module docstring)."""
    if cnt <= SPLIT_LEAF_IDS:
        high, low = divmod(s, alphabet ** cnt)
        low = _pop_loop(low, out, a, cnt, alphabet)
        return high * math.perm(a + cnt - 1, cnt) + low
    m = cnt // 2
    high, low = divmod(s, alphabet ** m)
    low = _pop_split(low, out, a, m, alphabet)
    s = high * math.perm(a + m - 1, m) + low    # perm = a(a+1)..(a+m-1)
    return _pop_split(s, out, a + m, cnt - m, alphabet)


def roc_encode_clusters(
    lists: Sequence[np.ndarray], alphabet: int, joint: bool = False
) -> List[BigANS]:
    """Encode inverted lists.

    ``joint=False`` — the paper's *online* setting: one stream per cluster
    (partial random access).  ``joint=True`` — the *offline* setting: all
    clusters share one stream (decoded back-to-front), amortizing nothing
    here (BigANS has no initial bits) but producing a single blob.
    """
    if joint:
        ans = BigANS()
        for ids in lists:
            roc_push_set(ans, ids, alphabet)
        return [ans]
    return [_encode_one(ids, alphabet) for ids in lists]


def _encode_one(ids: np.ndarray, alphabet: int) -> BigANS:
    ans = BigANS()
    roc_push_set(ans, ids, alphabet)
    return ans


def roc_decode_clusters(
    streams: Sequence[BigANS], sizes: Sequence[int], alphabet: int, joint: bool = False
) -> List[np.ndarray]:
    if joint:
        (ans,) = streams
        out = [roc_pop_set(ans, n, alphabet) for n in reversed(list(sizes))]
        return out[::-1]
    return [roc_pop_set(a, n, alphabet) for a, n in zip(streams, sizes)]


def set_information_bits(alphabet: int, n: int) -> float:
    """``log2 C(alphabet, n)`` — the information content of an n-subset."""
    return (
        math.lgamma(alphabet + 1)
        - math.lgamma(n + 1)
        - math.lgamma(alphabet - n + 1)
    ) / math.log(2)
