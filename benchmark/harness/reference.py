"""The plain reference: what a node's data directory must hold for a block.

Written from the configuration's statement of the code, not from the
program: Reed-Solomon over GF(2^8) with the polynomial 0x11d, the
systematic generator [I_k ; C] with the Cauchy matrix C[i][j] =
1 / ((k + i) xor j), pieces hashed with BLAKE3, blocks named by
BLAKE2b-512 cut to 32 bytes, a piece file = magic, block length, piece
hash, piece.  It imports nothing of `garage_tpu` and takes nothing the
program has made.  numpy only: the BLAKE3 here hashes many equal-length
inputs at once, lane by lane, so that hundreds of pieces take a second.
"""

from __future__ import annotations

import hashlib

import numpy as np

POLY = 0x11D
PIECE_MAGIC = b"GTP2"
SHARD_ALIGN = 64

# --- GF(2^8) -------------------------------------------------------------------


def _tables() -> tuple[np.ndarray, np.ndarray]:
    exp, log = [0] * 510, [0] * 256
    x = 1
    for i in range(255):
        exp[i] = exp[i + 255] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= POLY
    mul = np.zeros((256, 256), dtype=np.uint8)
    for a in range(1, 256):
        for b in range(1, 256):
            mul[a, b] = exp[log[a] + log[b]]
    inv = np.zeros(256, dtype=np.uint8)
    for a in range(1, 256):
        inv[a] = exp[255 - log[a]]
    return mul, inv


MUL, INV = _tables()


def generator(k: int, m: int) -> np.ndarray:
    """(k+m) x k: identity over the Cauchy parity rows."""
    g = np.zeros((k + m, k), dtype=np.uint8)
    for j in range(k):
        g[j, j] = 1
    for i in range(m):
        for j in range(k):
            g[k + i, j] = INV[(k + i) ^ j]
    return g


def gf_apply(mat: np.ndarray, shards: np.ndarray) -> np.ndarray:
    """(r x q) matrix times (q, S) shards over GF(2^8) -> (r, S)."""
    r, q = mat.shape
    out = np.zeros((r, shards.shape[1]), dtype=np.uint8)
    for i in range(r):
        for j in range(q):
            c = int(mat[i, j])
            if c:
                out[i] ^= MUL[c][shards[j]]
    return out


def gf_invert(a: np.ndarray) -> np.ndarray:
    n = a.shape[0]
    aug = np.concatenate([a.astype(np.uint8), np.eye(n, dtype=np.uint8)], axis=1)
    for col in range(n):
        piv = next(r for r in range(col, n) if aug[r, col])
        if piv != col:
            aug[[col, piv]] = aug[[piv, col]]
        aug[col] = MUL[int(INV[aug[col, col]])][aug[col]]
        for r in range(n):
            if r != col and aug[r, col]:
                aug[r] ^= MUL[int(aug[r, col])][aug[col]]
    return aug[:, n:]


def piece_len(block_len: int, k: int) -> int:
    s = -(-block_len // k)
    return -(-s // SHARD_ALIGN) * SHARD_ALIGN


def split_block(block: bytes, k: int) -> np.ndarray:
    s = piece_len(len(block), k)
    buf = np.zeros(k * s, dtype=np.uint8)
    buf[: len(block)] = np.frombuffer(block, dtype=np.uint8)
    return buf.reshape(k, s)


def encode_pieces(block: bytes, k: int, m: int) -> np.ndarray:
    """All k+m pieces of a block, (k+m, S)."""
    data = split_block(block, k)
    return np.concatenate([data, gf_apply(generator(k, m)[k:], data)])


def reconstruct(pieces: dict[int, np.ndarray], want: list[int], k: int, m: int) -> np.ndarray:
    """The `want` pieces from any k surviving ones."""
    present = sorted(pieces)[:k]
    g = generator(k, m)
    data = gf_apply(gf_invert(g[present]), np.stack([pieces[i] for i in present]))
    return gf_apply(g[want], data)


# --- BLAKE3, many equal-length inputs at once ------------------------------------

_IV = np.array(
    [0x6A09E667, 0xBB67AE85, 0x3C6EF372, 0xA54FF53A,
     0x510E527F, 0x9B05688C, 0x1F83D9AB, 0x5BE0CD19], dtype=np.uint32)
_PERM = [2, 6, 3, 10, 7, 0, 4, 13, 1, 11, 12, 5, 9, 14, 15, 8]
_CHUNK_START, _CHUNK_END, _PARENT, _ROOT = 1, 2, 4, 8


def _rotr(x: np.ndarray, n: int) -> np.ndarray:
    return (x >> np.uint32(n)) | (x << np.uint32(32 - n))


def _compress(cv, block, counter, block_len: int, flags: int):
    """cv (8, N), block (16, N) uint32 words, counter (N,) uint64 ->
    the first 8 output words, (8, N)."""
    n = cv.shape[1]
    v = [cv[i].copy() for i in range(8)] + [
        np.full(n, _IV[i], dtype=np.uint32) for i in range(4)
    ] + [
        (counter & np.uint64(0xFFFFFFFF)).astype(np.uint32),
        (counter >> np.uint64(32)).astype(np.uint32),
        np.full(n, block_len, dtype=np.uint32),
        np.full(n, flags, dtype=np.uint32),
    ]
    m = [block[i] for i in range(16)]

    def g(a, b, c, d, mx, my):
        v[a] = v[a] + v[b] + mx
        v[d] = _rotr(v[d] ^ v[a], 16)
        v[c] = v[c] + v[d]
        v[b] = _rotr(v[b] ^ v[c], 12)
        v[a] = v[a] + v[b] + my
        v[d] = _rotr(v[d] ^ v[a], 8)
        v[c] = v[c] + v[d]
        v[b] = _rotr(v[b] ^ v[c], 7)

    for rnd in range(7):
        g(0, 4, 8, 12, m[0], m[1])
        g(1, 5, 9, 13, m[2], m[3])
        g(2, 6, 10, 14, m[4], m[5])
        g(3, 7, 11, 15, m[6], m[7])
        g(0, 5, 10, 15, m[8], m[9])
        g(1, 6, 11, 12, m[10], m[11])
        g(2, 7, 8, 13, m[12], m[13])
        g(3, 4, 9, 14, m[14], m[15])
        if rnd < 6:
            m = [m[p] for p in _PERM]
    return np.stack([v[i] ^ v[i + 8] for i in range(8)])


def blake3_rows(rows: np.ndarray) -> np.ndarray:
    """BLAKE3 (32-byte digest) of every row of a (N, L) uint8 array."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    n, length = rows.shape
    n_chunks = max(1, -(-length // 1024))
    padded = np.zeros((n, n_chunks * 1024), dtype=np.uint8)
    padded[:, :length] = rows
    # (chunk, block, word, lane)
    words = padded.view("<u4").reshape(n, n_chunks, 16, 16).transpose(1, 2, 3, 0)
    iv = np.repeat(_IV[:, None], n, axis=1)
    cvs = []
    with np.errstate(over="ignore"):
        for c in range(n_chunks):
            clen = min(1024, length - c * 1024) if length else 0
            n_blocks = max(1, -(-clen // 64))
            cv = iv
            counter = np.full(n, c, dtype=np.uint64)
            for b in range(n_blocks):
                flags = (_CHUNK_START if b == 0 else 0) | (_CHUNK_END if b == n_blocks - 1 else 0)
                if n_chunks == 1 and b == n_blocks - 1:
                    flags |= _ROOT
                blen = min(64, clen - b * 64) if clen else 0
                cv = _compress(cv, words[c, b], counter, blen, flags)
            cvs.append(cv)
        zero = np.zeros(n, dtype=np.uint64)

        def merge(nodes: list, root: bool):
            """Left subtree: the largest power of two of chunks below the count."""
            if len(nodes) == 1:
                return nodes[0]
            left = 1 << ((len(nodes) - 1).bit_length() - 1)
            l, r = merge(nodes[:left], False), merge(nodes[left:], False)
            return _compress(iv, np.concatenate([l, r]), zero, 64, _PARENT | (_ROOT if root else 0))

        out = merge(cvs, True)
    return np.ascontiguousarray(out.T).view(np.uint8).reshape(n, 32)


# --- what a data directory must hold -----------------------------------------------


def block_hash(block: bytes) -> bytes:
    return hashlib.blake2b(block).digest()[:32]


def piece_file(block_len: int, piece: bytes, phash: bytes) -> bytes:
    return PIECE_MAGIC + block_len.to_bytes(8, "big") + phash + piece


def expected_piece_files(blocks: list[bytes], k: int, m: int, ranks=None) -> list[dict[int, bytes]]:
    """For each block the stored file of every piece rank in `ranks`
    (all k+m when None).  Blocks of one length are hashed together."""
    ranks = list(range(k + m)) if ranks is None else list(ranks)
    need_parity = any(r >= k for r in ranks)
    out: list[dict[int, bytes]] = [{} for _ in blocks]
    by_len: dict[int, list[int]] = {}
    for i, b in enumerate(blocks):
        by_len.setdefault(len(b), []).append(i)
    for blen, idxs in by_len.items():
        rows, where = [], []
        for i in idxs:
            pieces = encode_pieces(blocks[i], k, m) if need_parity else split_block(blocks[i], k)
            for r in ranks:
                rows.append(pieces[r])
                where.append((i, r))
        hashes = blake3_rows(np.stack(rows))
        for (i, r), row, h in zip(where, rows, hashes):
            out[i][r] = piece_file(blen, row.tobytes(), h.tobytes())
    return out
