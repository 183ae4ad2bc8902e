"""The least bytes one scheduling round's solve must move, from shapes only.

Counted: what ANY implementation has to touch.  Each pass reads the pod
operands (request vector, priority, QoS: R + 2 int32 per padded pod row) and
the node operands (allocatable, requested, usage: 3 R int32 per node) once;
the round writes one int32 assignment per padded pod row and the updated
requested row of every node once.  Not counted: any intermediate an
implementation may choose not to materialise (the P x N score plane,
candidate lists, per-round proposals).  So no later kernel can push a share
of the roofline built on this count past 100 %.
"""

from __future__ import annotations

INT32 = 4


def pad_pow2(pods: int) -> int:
    """The power-of-two pod bucket a round of ``pods`` pods is padded to."""
    return 1 << max(pods - 1, 0).bit_length()


def least_bytes(p_pad: int, n: int, r: int, passes: int) -> int:
    if min(p_pad, n, r, passes) < 1:
        raise ValueError("shapes and passes must be positive")
    read = passes * (p_pad * (r + 2) + n * 3 * r) * INT32
    written = (p_pad + n * r) * INT32
    return read + written
