"""MessagePack-RPC framing as the servers speak it: a request is
``[0, msgid, method, params]``, an answer ``[1, msgid, error, result]``.

Requests are encoded once, before the window; the generator sends the
bytes and patches the message id, which is always packed as a 5-byte
uint32 so that the patch is a fixed slice."""

from __future__ import annotations

import struct
from typing import Any, List, Sequence, Tuple

import msgpack

REQUEST = 0
RESPONSE = 1
_MSGID_AT = slice(3, 7)  # 0x94, 0x00, 0xce, <4 bytes of msgid>


def encode_request(method: str, params: Sequence[Any]) -> bytearray:
    """One request with message id 0, ready for :func:`with_msgid`."""
    head = b"\x94\x00\xce\x00\x00\x00\x00" + msgpack.packb(method)
    return bytearray(head + msgpack.packb(list(params), use_single_float=False))


def with_msgid(frame: bytearray, msgid: int) -> bytearray:
    frame[_MSGID_AT] = struct.pack(">I", msgid & 0xFFFFFFFF)
    return frame


def datum(strings: Sequence[Tuple[str, str]],
          nums: Sequence[Tuple[str, float]]) -> List[Any]:
    """A Jubatus datum on the wire: three lists of key/value pairs (a
    pair may stay a tuple: msgpack packs it as the same array)."""
    return [strings, nums, []]


def answer(msg: Any) -> Tuple[int, Any, Any]:
    """(msgid, error, result) of one decoded answer; raises on anything
    that is not an answer."""
    if not isinstance(msg, (list, tuple)) or len(msg) != 4 \
            or msg[0] != RESPONSE:
        raise ValueError(f"not a msgpack-rpc answer: {msg!r:.200}")
    return int(msg[1]), msg[2], msg[3]
