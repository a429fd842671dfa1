/* SIMD region kernels for GF(2^8) multiply-accumulate.
 *
 * The honest CPU baseline the TPU path is measured against: the same
 * techniques the reference's isa-l submodule uses on x86 —
 * GF2P8AFFINEQB (GFNI) where available, else the classic split-nibble
 * PSHUFB trick (isa-l's gf_vect_mul/gf_Nvect_mad family; cf. the
 * reference wiring at src/erasure-code/isa/ErasureCodeIsa.cc:119-131
 * ec_encode_data).  Structure follows isa-l's mad kernels: iterate over
 * 32-byte position blocks, keep all nout accumulators in registers, and
 * stream each input region exactly once, so the pass is memory-minimal
 * (k reads + m writes total, not k*m passes).
 *
 * Field semantics are gf8's poly 0x11D; GFNI's GF2P8MULB is hardwired to
 * 0x11B so only the *affine* instruction is usable: multiplication by a
 * constant c is linear over GF(2), i.e. one 8x8 bit-matrix per
 * coefficient, applied by GF2P8AFFINEQB in any field representation.
 */
#include "gf8.h"

#include <immintrin.h>

#include <cstring>
#include <ctime>
#include <mutex>
#include <vector>

namespace gf8 {

int simd_level() {
    static int level = [] {
        __builtin_cpu_init();
        if (__builtin_cpu_supports("gfni") &&
            __builtin_cpu_supports("avx512f") &&
            __builtin_cpu_supports("avx512bw"))
            return 3;
        if (__builtin_cpu_supports("gfni") && __builtin_cpu_supports("avx2"))
            return 2;
        if (__builtin_cpu_supports("avx2")) return 1;
        return 0;
    }();
    return level;
}

namespace {

/* scalar cleanup for the <32-byte tail of each region */
void scalar_tail(const uint8_t *coef, int nout, int nin,
                 const uint8_t *const *in, uint8_t *const *out,
                 size_t from, size_t to) {
    for (int r = 0; r < nout; r++) {
        uint8_t *dst = out[r];
        for (size_t i = from; i < to; i++) dst[i] = 0;
        for (int j = 0; j < nin; j++) {
            uint8_t c = coef[(size_t)r * nin + j];
            if (!c) continue;
            const uint8_t *row = MUL[c];
            const uint8_t *srcp = in[j];
            for (size_t i = from; i < to; i++) dst[i] ^= row[srcp[i]];
        }
    }
}

/* 8x8 GF(2) bit-matrix for multiplication by c, in GF2P8AFFINEQB's layout:
 * qword byte (7-q) holds the row producing output bit q; row bit p
 * multiplies input bit p (Intel SDM affine_byte operation). */
uint64_t affine_qword(uint8_t c) {
    uint64_t a = 0;
    for (int q = 0; q < 8; q++) {
        uint8_t row = 0;
        for (int p = 0; p < 8; p++)
            if ((MUL[c][1u << p] >> q) & 1) row |= (uint8_t)(1u << p);
        a |= (uint64_t)row << (8 * (7 - q));
    }
    return a;
}

constexpr int MAX_ACC = 8;   /* register accumulators per position block */

__attribute__((target("gfni,avx2")))
void block_pass_gfni(const uint8_t *coef, int nout, int nin,
                     const uint8_t *const *in, uint8_t *const *out,
                     size_t blocks) {
    /* precompute the affine matrix per (r, j) coefficient */
    __m256i mats[MAX_ACC * 32];
    for (int r = 0; r < nout; r++)
        for (int j = 0; j < nin; j++)
            mats[r * nin + j] = _mm256_set1_epi64x(
                (long long)affine_qword(coef[(size_t)r * nin + j]));
    for (size_t b = 0; b < blocks; b++) {
        const size_t off = b * 32;
        __m256i acc[MAX_ACC];
        for (int r = 0; r < nout; r++) acc[r] = _mm256_setzero_si256();
        for (int j = 0; j < nin; j++) {
            __m256i x = _mm256_loadu_si256(
                (const __m256i *)(in[j] + off));
            for (int r = 0; r < nout; r++) {
                uint8_t c = coef[(size_t)r * nin + j];
                if (!c) continue;
                acc[r] = _mm256_xor_si256(
                    acc[r],
                    _mm256_gf2p8affine_epi64_epi8(x, mats[r * nin + j], 0));
            }
        }
        for (int r = 0; r < nout; r++)
            _mm256_storeu_si256((__m256i *)(out[r] + off), acc[r]);
    }
}

__attribute__((target("gfni,avx512f,avx512bw")))
void block_pass_gfni512(const uint8_t *coef, int nout, int nin,
                        const uint8_t *const *in, uint8_t *const *out,
                        size_t blocks64) {
    __m512i mats[MAX_ACC * 32];
    for (int r = 0; r < nout; r++)
        for (int j = 0; j < nin; j++)
            mats[r * nin + j] = _mm512_set1_epi64(
                (long long)affine_qword(coef[(size_t)r * nin + j]));
    for (size_t b = 0; b < blocks64; b++) {
        const size_t off = b * 64;
        __m512i acc[MAX_ACC];
        for (int r = 0; r < nout; r++) acc[r] = _mm512_setzero_si512();
        for (int j = 0; j < nin; j++) {
            __m512i x = _mm512_loadu_si512(
                (const void *)(in[j] + off));
            for (int r = 0; r < nout; r++) {
                uint8_t c = coef[(size_t)r * nin + j];
                if (!c) continue;
                acc[r] = _mm512_xor_si512(
                    acc[r],
                    _mm512_gf2p8affine_epi64_epi8(x, mats[r * nin + j], 0));
            }
        }
        for (int r = 0; r < nout; r++)
            _mm512_storeu_si512((void *)(out[r] + off), acc[r]);
    }
}

__attribute__((target("avx2")))
void block_pass_avx2(const uint8_t *coef, int nout, int nin,
                     const uint8_t *const *in, uint8_t *const *out,
                     size_t blocks) {
    /* split-nibble tables per (r, j): lo[i] = c*i, hi[i] = c*(i<<4),
     * broadcast to both 128-bit lanes for VPSHUFB */
    __m256i tlo[MAX_ACC * 32], thi[MAX_ACC * 32];
    for (int r = 0; r < nout; r++)
        for (int j = 0; j < nin; j++) {
            uint8_t c = coef[(size_t)r * nin + j];
            alignas(32) uint8_t lo[32], hi[32];
            for (int i = 0; i < 16; i++) {
                lo[i] = lo[i + 16] = MUL[c][i];
                hi[i] = hi[i + 16] = MUL[c][i << 4];
            }
            tlo[r * nin + j] = _mm256_load_si256((const __m256i *)lo);
            thi[r * nin + j] = _mm256_load_si256((const __m256i *)hi);
        }
    const __m256i nib = _mm256_set1_epi8(0x0f);
    for (size_t b = 0; b < blocks; b++) {
        const size_t off = b * 32;
        __m256i acc[MAX_ACC];
        for (int r = 0; r < nout; r++) acc[r] = _mm256_setzero_si256();
        for (int j = 0; j < nin; j++) {
            __m256i x = _mm256_loadu_si256(
                (const __m256i *)(in[j] + off));
            __m256i xl = _mm256_and_si256(x, nib);
            __m256i xh = _mm256_and_si256(_mm256_srli_epi64(x, 4), nib);
            for (int r = 0; r < nout; r++) {
                uint8_t c = coef[(size_t)r * nin + j];
                if (!c) continue;
                __m256i p = _mm256_xor_si256(
                    _mm256_shuffle_epi8(tlo[r * nin + j], xl),
                    _mm256_shuffle_epi8(thi[r * nin + j], xh));
                acc[r] = _mm256_xor_si256(acc[r], p);
            }
        }
        for (int r = 0; r < nout; r++)
            _mm256_storeu_si256((__m256i *)(out[r] + off), acc[r]);
    }
}

bool gfni_verified() {
    /* one-time self-check of the affine bit convention against the
     * scalar tables; falls back to pshufb if the layout ever mismatches */
    static bool ok = [] {
        gf8::init_tables();  /* the check compares against MUL; an empty
                              * table would vacuously pass and pin GFNI on */
        if (simd_level() < 2) return false;
        alignas(32) uint8_t src[32], dst[32];
        for (int i = 0; i < 32; i++) src[i] = (uint8_t)(i * 7 + 3);
        const uint8_t coef = 0x8e;   /* a full-width constant */
        const uint8_t *inp[1] = {src};
        uint8_t *outp[1] = {dst};
        block_pass_gfni(&coef, 1, 1, inp, outp, 1);
        for (int i = 0; i < 32; i++)
            if (dst[i] != MUL[coef][src[i]]) return false;
        return true;
    }();
    return ok;
}

}  // namespace

bool simd_apply_matrix_ptrs(const uint8_t *coef, int nout, int nin,
                            const uint8_t *const *in, uint8_t *const *out,
                            size_t chunk_size) {
    if (nout <= 0 || nin <= 0 || nin > 32 || chunk_size < 32)
        return false;
    int level = simd_level();
    if (level == 0) return false;
    const bool gfni = level >= 2 && gfni_verified();
    const bool wide = gfni && level >= 3 && chunk_size >= 64;
    /* zmm path handles 64-byte blocks; remainder falls to the 32-byte
     * ymm pass, then a scalar tail */
    size_t blocks64 = wide ? chunk_size / 64 : 0;
    size_t done = blocks64 * 64;
    size_t blocks32 = (chunk_size - done) / 32;
    /* wide outputs run in register-sized row groups */
    for (int r0 = 0; r0 < nout; r0 += MAX_ACC) {
        int rows = nout - r0 < MAX_ACC ? nout - r0 : MAX_ACC;
        const uint8_t *c0 = coef + (size_t)r0 * nin;
        uint8_t *const *o0 = out + r0;
        if (wide)
            block_pass_gfni512(c0, rows, nin, in, o0, blocks64);
        if (blocks32) {
            const uint8_t *inp32[32];
            uint8_t *outp32[MAX_ACC];
            for (int j = 0; j < nin; j++) inp32[j] = in[j] + done;
            for (int r = 0; r < rows; r++) outp32[r] = o0[r] + done;
            if (gfni)
                block_pass_gfni(c0, rows, nin, inp32, outp32, blocks32);
            else
                block_pass_avx2(c0, rows, nin, inp32, outp32, blocks32);
        }
        size_t vec_done = done + blocks32 * 32;
        if (vec_done < chunk_size)
            scalar_tail(c0, rows, nin, in, o0, vec_done, chunk_size);
    }
    return true;
}

}  // namespace gf8

/* C entry points for introspection and in-process benchmarking (no
 * Python/ctypes overhead in the timed loop). */
extern "C" int ec_simd_level(void) { return gf8::simd_level(); }

extern "C" double ec_bench_apply(int nout, int nin, size_t chunk_size,
                                 int iters) {
    gf8::init_tables();
    std::vector<uint8_t> coef((size_t)nout * nin);
    for (size_t i = 0; i < coef.size(); i++) coef[i] = (uint8_t)(i * 37 + 5);
    std::vector<std::vector<uint8_t>> in(nin), out(nout);
    std::vector<const uint8_t *> inp;
    std::vector<uint8_t *> outp;
    for (int j = 0; j < nin; j++) {
        in[j].resize(chunk_size);
        for (size_t i = 0; i < chunk_size; i++)
            in[j][i] = (uint8_t)(i + j);
        inp.push_back(in[j].data());
    }
    for (int r = 0; r < nout; r++) {
        out[r].resize(chunk_size);
        outp.push_back(out[r].data());
    }
    gf8::apply_matrix_ptrs(coef.data(), nout, nin, inp.data(), outp.data(),
                           chunk_size);   /* warm */
    struct timespec t0, t1;
    clock_gettime(CLOCK_MONOTONIC, &t0);
    for (int i = 0; i < iters; i++)
        gf8::apply_matrix_ptrs(coef.data(), nout, nin, inp.data(),
                               outp.data(), chunk_size);
    clock_gettime(CLOCK_MONOTONIC, &t1);
    return (t1.tv_sec - t0.tv_sec) + (t1.tv_nsec - t0.tv_nsec) * 1e-9;
}

extern "C" int ec_apply_matrix(const unsigned char *coef, int nout, int nin,
                               const unsigned char *in, unsigned char *out,
                               size_t chunk_size) {
    gf8::init_tables();
    gf8::apply_matrix(coef, nout, nin, in, out, chunk_size);
    return 0;
}

/* crc32c (Castagnoli), raw reflected update without final xor — the
 * ceph_crc32c contract HashInfo chains per shard.  SSE4.2's CRC32
 * instruction computes exactly this polynomial; scalar slice-by-8
 * fallback elsewhere. */
namespace {

uint32_t crc32c_sw(uint32_t crc, const unsigned char *p, size_t n) {
    static uint32_t T[8][256];
    static std::once_flag once;
    std::call_once(once, [] {
        for (uint32_t i = 0; i < 256; i++) {
            uint32_t c = i;
            for (int j = 0; j < 8; j++)
                c = (c & 1) ? (c >> 1) ^ 0x82F63B78u : c >> 1;
            T[0][i] = c;
        }
        for (int t = 1; t < 8; t++)
            for (uint32_t i = 0; i < 256; i++)
                T[t][i] = (T[t - 1][i] >> 8) ^ T[0][T[t - 1][i] & 0xFF];
    });
    while (n >= 8) {
        crc ^= (uint32_t)p[0] | ((uint32_t)p[1] << 8) |
               ((uint32_t)p[2] << 16) | ((uint32_t)p[3] << 24);
        crc = T[7][crc & 0xFF] ^ T[6][(crc >> 8) & 0xFF] ^
              T[5][(crc >> 16) & 0xFF] ^ T[4][crc >> 24] ^
              T[3][p[4]] ^ T[2][p[5]] ^ T[1][p[6]] ^ T[0][p[7]];
        p += 8;
        n -= 8;
    }
    while (n--) {
        crc = T[0][(crc ^ *p++) & 0xFF] ^ (crc >> 8);
    }
    return crc;
}

__attribute__((target("sse4.2")))
uint32_t crc32c_hw(uint32_t crc, const unsigned char *p, size_t n) {
    uint64_t c = crc;
    while (n >= 8) {
        uint64_t v;
        std::memcpy(&v, p, 8);
        c = _mm_crc32_u64(c, v);
        p += 8;
        n -= 8;
    }
    uint32_t c32 = (uint32_t)c;
    while (n--) c32 = _mm_crc32_u8(c32, *p++);
    return c32;
}

}  // namespace

extern "C" uint32_t ec_crc32c(uint32_t seed, const unsigned char *p,
                              size_t n) {
    __builtin_cpu_init();
    static const bool hw = __builtin_cpu_supports("sse4.2");
    return hw ? crc32c_hw(seed, p, n) : crc32c_sw(seed, p, n);
}

/* One crc32c per ``block`` bytes of ``p`` (the last block may be short),
 * each seeded with 0xFFFFFFFF, stored as little-endian u32 at ``out``:
 * a BlueStore blob's per-csum-chunk checksums in one call. */
extern "C" void ec_crc32c_blocks(const unsigned char *p, size_t n,
                                 size_t block, unsigned char *out) {
    if (block == 0) return;
    __builtin_cpu_init();
    static const bool hw = __builtin_cpu_supports("sse4.2");
    for (size_t off = 0; off < n; off += block, out += 4) {
        size_t len = n - off < block ? n - off : block;
        uint32_t c = hw ? crc32c_hw(0xFFFFFFFFu, p + off, len)
                        : crc32c_sw(0xFFFFFFFFu, p + off, len);
        out[0] = (unsigned char)c;
        out[1] = (unsigned char)(c >> 8);
        out[2] = (unsigned char)(c >> 16);
        out[3] = (unsigned char)(c >> 24);
    }
}
