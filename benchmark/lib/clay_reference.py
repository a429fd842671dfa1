"""The plain reference for Clay codes: the encode of Vajha et al., "Clay
Codes: Moulding MDS Codes to Yield an MSR Code" (FAST 2018), as
upstream's ``ErasureCodeClay`` computes it with ``scalar_mds=jax_rs
technique=cauchy``, in numpy.

Independent of the program under test: imports nothing from
``ceph_tpu`` and builds on :mod:`benchmark.lib.reference`'s GF(2^8)
(polynomial 0x11D).  The code, from the paper and upstream's
``ErasureCodeClay.{h,cc}``:

- q = d - k + 1 and nu shortened (all-zero) nodes make k + m + nu = q*t
  nodes on a q x t grid, node = y*q + x, data nodes first, then the nu
  zero nodes, then the m parity nodes; a chunk has Z = q^t planes
  (sub-chunks), plane z written as t base-q digits, most significant
  first;
- a point (x, y, z) whose digit z_y is x is a dot: its uncoupled value
  U is its coupled (stored) value C.  Any other point pairs with
  (z_y, y, z'), z' being z with digit y set to x; with "hi" the point
  of the larger x, the pairwise transform is the RS(2, 2) code
  [U_hi, U_lo] = P [C_hi, C_lo], P the technique=cauchy parity of
  k=2, m=2;
- in every plane the uncoupled values of all nodes are a codeword of
  the scalar MDS code: U_parity = M U_rest, M the technique=cauchy
  parity of k+nu, m.

The encode solves the parity nodes plane layer by plane layer: a
plane's layer is how many parity nodes have their dot in it, and a
plane of layer s needs coupled parity values only of layer s - 1.
Vectorised over stripes and bytes only: every value is one sub-chunk
of every stripe, ``[S, C/Z]``.
"""
from __future__ import annotations

import numpy as np

from . import reference as R


def _mul(c: int, v: np.ndarray) -> np.ndarray:
    return R.MUL[c][v]


class Clay:
    def __init__(self, k: int, m: int, d: int):
        if not k <= d <= k + m - 1:
            raise ValueError(f"d={d} not in [{k}, {k + m - 1}]")
        self.k, self.m, self.d = k, m, d
        self.q = d - k + 1
        self.nu = (self.q - (k + m) % self.q) % self.q
        self.t = (k + m + self.nu) // self.q
        self.planes = self.q ** self.t
        self.mds = R.cauchy_parity_matrix(k + self.nu, m)
        self.pft = R.cauchy_parity_matrix(2, 2)

    def digit(self, z: int, y: int) -> int:
        return (z // self.q ** (self.t - 1 - y)) % self.q

    def partner_plane(self, z: int, y: int, x: int) -> int:
        return z + (x - self.digit(z, y)) * self.q ** (self.t - 1 - y)

    def node(self, chunk: int) -> int:
        return chunk if chunk < self.k else chunk + self.nu

    def repair_planes(self, chunk: int) -> list[int]:
        """The planes every helper sends to rebuild ``chunk``: those whose
        digit y is x, (x, y) being the lost node."""
        y, x = divmod(self.node(chunk), self.q)
        return [z for z in range(self.planes) if self.digit(z, y) == x]

    # -- the pairwise transform ----------------------------------------------------

    def _uncouple(self, c_self, c_other, self_hi: bool):
        """U of a point from its C and its partner's C."""
        (p00, p01), (p10, p11) = (int(v) for v in self.pft[0]), \
            (int(v) for v in self.pft[1])
        if self_hi:
            return _mul(p00, c_self) ^ _mul(p01, c_other)
        return _mul(p10, c_other) ^ _mul(p11, c_self)

    def _couple_from_partner(self, u_self, c_other, self_hi: bool):
        """C of a point from its U and its partner's C:
        U_hi = p00 C_hi + p01 C_lo, U_lo = p10 C_hi + p11 C_lo."""
        (p00, p01), (p10, p11) = (int(v) for v in self.pft[0]), \
            (int(v) for v in self.pft[1])
        if self_hi:
            return _mul(R.gf_inv(p00), u_self ^ _mul(p01, c_other))
        return _mul(R.gf_inv(p11), u_self ^ _mul(p10, c_other))

    def _couple_pair(self, u_hi, u_lo):
        """[C_hi, C_lo] = P^-1 [U_hi, U_lo]."""
        inv = R.gf_invert_matrix(self.pft)
        return (_mul(int(inv[0, 0]), u_hi) ^ _mul(int(inv[0, 1]), u_lo),
                _mul(int(inv[1, 0]), u_hi) ^ _mul(int(inv[1, 1]), u_lo))

    # -- the encode -----------------------------------------------------------------

    def encode(self, data: np.ndarray, chunk: int) -> np.ndarray:
        """[k, S*chunk] -> the m parity chunks [m, S*chunk], each stripe's
        chunk one codeword of Z sub-chunks."""
        data = np.asarray(data, dtype=np.uint8)
        k, m, q, Z = self.k, self.m, self.q, self.planes
        n = data.shape[1]
        stripes, sc = n // chunk, chunk // Z
        nodes = q * self.t
        zero = np.zeros((stripes, sc), dtype=np.uint8)
        C = [[zero] * Z for _ in range(nodes)]
        U = [[None] * Z for _ in range(nodes)]
        cut = data.reshape(k, stripes, Z, sc)
        for i in range(k):
            C[i] = [cut[i, :, z] for z in range(Z)]
        parity = list(range(k + self.nu, nodes))
        layer = {}
        for z in range(Z):
            s = sum(self.digit(z, p // q) == p % q for p in parity)
            layer.setdefault(s, []).append(z)
        for s in sorted(layer):
            for z in layer[s]:
                for node in range(k + self.nu):
                    y, x = divmod(node, q)
                    a = self.digit(z, y)
                    if a == x:
                        U[node][z] = C[node][z]
                    else:
                        zp = self.partner_plane(z, y, x)
                        U[node][z] = self._uncouple(C[node][z],
                                                    C[y * q + a][zp], x > a)
                for j, p in enumerate(parity):
                    acc = np.zeros((stripes, sc), dtype=np.uint8)
                    for i in range(k + self.nu):
                        acc ^= _mul(int(self.mds[j, i]), U[i][z])
                    U[p][z] = acc
            for z in layer[s]:
                for p in parity:
                    y, x = divmod(p, q)
                    a = self.digit(z, y)
                    partner = y * q + a
                    zp = self.partner_plane(z, y, x)
                    if a == x:
                        C[p][z] = U[p][z]
                    elif partner not in parity:
                        C[p][z] = self._couple_from_partner(
                            U[p][z], C[partner][zp], x > a)
                    elif x > a:
                        C[p][z], C[partner][zp] = self._couple_pair(
                            U[p][z], U[partner][zp])
        out = np.stack([np.stack(C[p], axis=1) for p in parity])
        return out.reshape(m, n)

    def codeword(self, data: np.ndarray, chunk: int) -> np.ndarray:
        """[k, S*chunk] -> all k + m chunks."""
        data = np.asarray(data, dtype=np.uint8)
        return np.concatenate([data, self.encode(data, chunk)], axis=0)
