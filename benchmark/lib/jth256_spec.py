"""JTH-256, the plain reference: the normative definition in
`juicefs_tpu/tpu/jth256.py`'s docstring written out in numpy, vectorised
over a block's lanes so that some hundreds of 4 MiB blocks take seconds.
The benchmark's own copy: imports nothing of the program, takes nothing
the program made.

  lane_compress(W[128][128], lane):
      s[j]   = P5 ^ (j*P1) ^ (lane*P3)
      for r in [0,128): s = (s ^ W[r]) * P1; s = rotl(s,13) * P2; s ^= s >> 15
      G = s as [16][8];  acc[k] = P4 ^ (lane*P2) ^ (k*P1)
      for g in [0,16): acc = rotl((acc ^ G[g]) * P3, 11) + g*P5
  jth256(data): n = len(data); m = max(1, ceil(n/65536)); zero-pad to m lanes
      h = IV;  for i in [0,m): h = rotl((h ^ lane_compress(W[i], i)) * P2, 17) + i*P1
      h ^= n + k*P4;  h = fmix(h);  digest = h as uint32-LE (32 bytes)
"""

from __future__ import annotations

import numpy as np

LANE_BYTES = 65536
P1, P2, P3, P4, P5 = (np.uint32(x) for x in (
    0x9E3779B1, 0x85EBCA77, 0xC2B2AE3D, 0x27D4EB2F, 0x165667B1))
FM1, FM2 = np.uint32(0x85EBCA6B), np.uint32(0xC2B2AE35)
IV = np.array([0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
               0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19], dtype=np.uint32)
_J128 = np.arange(128, dtype=np.uint32)
_K8 = np.arange(8, dtype=np.uint32)


def _rotl(x, k):
    return (x << np.uint32(k)) | (x >> np.uint32(32 - k))


def lanes_of(n: int) -> int:
    return max(1, -(-n // LANE_BYTES))


def jth256(data: bytes) -> bytes:
    n = len(data)
    m = lanes_of(n)
    w = np.frombuffer(data + b"\0" * (m * LANE_BYTES - n), dtype="<u4")
    w = w.reshape(m, 128, 128).astype(np.uint32, copy=False)
    lane = np.arange(m, dtype=np.uint32)[:, None]
    s = P5 ^ (_J128 * P1)[None, :] ^ (lane * P3)
    for r in range(128):
        s = (s ^ w[:, r, :]) * P1
        s = _rotl(s, 13) * P2
        s = s ^ (s >> np.uint32(15))
    g = s.reshape(m, 16, 8)
    acc = P4 ^ (lane * P2) ^ (_K8 * P1)[None, :]
    for gi in range(16):
        acc = _rotl((acc ^ g[:, gi, :]) * P3, 11) + np.uint32((gi * int(P5)) & 0xFFFFFFFF)
    h = IV.copy()
    for i in range(m):
        h = _rotl((h ^ acc[i]) * P2, 17) + np.uint32((i * int(P1)) & 0xFFFFFFFF)
    h = h ^ (np.uint32(n) + _K8 * P4)
    h = h ^ (h >> np.uint32(16))
    h = h * FM1
    h = h ^ (h >> np.uint32(13))
    h = h * FM2
    h = h ^ (h >> np.uint32(16))
    return h.astype("<u4").tobytes()
