/* GF(2^8) region arithmetic — the native CPU engine.
 *
 * Role in this framework (SURVEY.md §8 stage 8): the reference's EC hot
 * loop is gf-complete's SIMD region multiply
 * (src/erasure-code/jerasure/gf-complete, galois_w08_region_multiply);
 * the TPU path replaces it with MXU matmuls, and THIS library is the
 * native-code analog for host-side work: the CPU fallback inside the
 * libec plugin bridge and the byte-exactness oracle reachable from C++
 * without Python.
 *
 * Field: GF(256), primitive polynomial 0x11d — identical tables to
 * ceph_tpu/ops/gf.py (tests assert this).
 *
 * Written as plain C-compatible functions so ctypes/cffi bind directly.
 */
#ifndef CEPH_TPU_GF256_H
#define CEPH_TPU_GF256_H

#include <stddef.h>
#include <stdint.h>

#ifdef __cplusplus
extern "C" {
#endif

/* One-time table setup (idempotent, thread-safe-enough: tables are
 * deterministic so racing initializers write identical bytes). */
void gf256_init(void);

/* Table accessors (for binding-level cross-checks). */
const uint8_t *gf256_mul_table(void);   /* [256*256] */
const uint8_t *gf256_inv_table(void);   /* [256] */

uint8_t gf256_mul(uint8_t a, uint8_t b);

/* region ops: dst[i] (op)= src[i] * c over GF(2^8), n bytes.
 * Dispatched over self-checked SIMD tiers: GFNI/AVX-512
 * (vgf2p8affineqb bit-matrix), AVX2 vpshufb split-nibble (the
 * gf-complete technique), scalar fallback. */
void gf256_region_mul(uint8_t *dst, const uint8_t *src, uint8_t c,
                      size_t n);

/* Force a dispatch tier for testing: 0=auto, 1=scalar, 2=avx2,
 * 3=gfni.  Returns the tier now in force, or -1 if the requested
 * tier is unavailable on this CPU (state unchanged). */
int gf256_set_tier(int tier);
void gf256_region_mul_xor(uint8_t *dst, const uint8_t *src, uint8_t c,
                          size_t n);

/* Reed-Solomon over chunk regions.
 * coding: [m][k] row-major generator (systematic part excluded).
 * data:   k pointers to chunk buffers of chunk_size bytes.
 * parity: m pointers, written. */
void gf256_rs_encode(const uint8_t *coding, int k, int m,
                     const uint8_t *const *data, uint8_t *const *parity,
                     size_t chunk_size);

/* Batched encode: stripes laid out [batch][k][chunk] contiguous in,
 * [batch][m][chunk] out — the coalescing ring's dispatch shape. */
void gf256_rs_encode_batch(const uint8_t *coding, int k, int m,
                           const uint8_t *data, uint8_t *parity,
                           size_t chunk_size, size_t batch);

/* Invert a k x k matrix over GF(2^8) (row-major, in place copy).
 * Returns 0 on success, -1 if singular. */
int gf256_mat_invert(const uint8_t *mat, uint8_t *inv, int k);

/* Decode: rebuild all k data chunks from any k surviving chunks.
 * survivors: ids (0..k+m-1) of the k chunks in `chunks` order.
 * Returns 0 on success, -1 on bad args / singular submatrix. */
int gf256_rs_decode(const uint8_t *coding, int k, int m,
                    const int *survivors, const uint8_t *const *chunks,
                    uint8_t *const *out_data, size_t chunk_size);

#ifdef __cplusplus
}
#endif

#endif /* CEPH_TPU_GF256_H */
