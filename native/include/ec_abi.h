/* C ABI for erasure-code plugins (the framework's native plugin contract).
 *
 * Mirror of the reference's plugin interface surface
 * (reference: src/erasure-code/ErasureCodeInterface.h:170-462 methods;
 * src/erasure-code/ErasureCodePlugin.{h,cc} registry + dlopen contract:
 * entry points __erasure_code_init/__erasure_code_version at
 * ErasureCodePlugin.cc:24-34, version check :144, "libec_<name>.so" prefix
 * :28) reshaped as a C vtable so codecs cross the C/Python boundary without
 * C++ name mangling: Python binds via ctypes.
 */
#ifndef CEPH_TPU_EC_ABI_H
#define CEPH_TPU_EC_ABI_H

#include <stddef.h>

#ifdef __cplusplus
extern "C" {
#endif

/* checked against each plugin's __erasure_code_version(), the analog of
 * the CEPH_GIT_NICE_VER comparison (ErasureCodePlugin.cc:139-150) */
#define EC_ABI_VERSION "ceph-tpu-ec-1"

/* dlopen name pattern (ErasureCodePlugin.cc:28) */
#define EC_PLUGIN_PREFIX "libec_"
#define EC_PLUGIN_SUFFIX ".so"

typedef struct ec_codec ec_codec; /* opaque per-instance state */

typedef struct ec_codec_ops {
    /* init(profile) -> instance; profile is parallel key/value arrays
     * (ErasureCodeProfile is map<string,string>, Interface.h:155).
     * Returns NULL and fills errbuf on bad profile. */
    ec_codec *(*create)(const char *const *prof_keys,
                        const char *const *prof_vals, int nprof,
                        char *errbuf, int errlen);
    void (*destroy)(ec_codec *);

    int (*get_data_chunk_count)(const ec_codec *);   /* k  (:237) */
    int (*get_chunk_count)(const ec_codec *);        /* k+m (:227) */
    /* chunk size for an object size, padded/aligned the way
     * ErasureCode::get_chunk_size + SIMD_ALIGN do (ErasureCode.cc:42,151) */
    unsigned (*get_chunk_size)(const ec_codec *, unsigned object_size);

    /* encode_chunks (:370): data = k contiguous chunks of chunk_size bytes,
     * parity out = m contiguous chunks.  Returns 0 or -errno. */
    int (*encode)(ec_codec *, const unsigned char *data,
                  unsigned char *parity, size_t chunk_size);

    /* decode_chunks (:411): chunks[i] for i in [0, k+m) point at
     * chunk_size-byte buffers; entries listed in erasures[] are outputs
     * (reconstructed in place), the rest are inputs.  Returns 0 or -errno. */
    int (*decode)(ec_codec *, unsigned char **chunks, size_t chunk_size,
                  const int *erasures, int n_erasures);

    /* minimum_to_decode (:297): fills want_out (cap n) with the chunk ids
     * to read for recovering `erasures` given `available`; returns count
     * or -EIO when unrecoverable. */
    int (*minimum_to_decode)(ec_codec *, const int *erasures, int n_erasures,
                             const int *available, int n_available,
                             int *want_out, int cap);
} ec_codec_ops;

/* ---- registry (exported by libec_registry.so) ------------------------- */

/* self-registration, called from a plugin's __erasure_code_init */
int ec_registry_add(const char *name, const ec_codec_ops *ops);
const ec_codec_ops *ec_registry_get(const char *name);
/* dlopen(directory/libec_<name>.so), verify version, run init
 * (ErasureCodePlugin.cc:126-184).  0 on success, -errno + errbuf else. */
int ec_registry_load(const char *name, const char *directory,
                     char *errbuf, int errlen);
/* comma-separated preload list (global_init preload_erasure_code,
 * option osd_erasure_code_plugins) */
int ec_registry_preload(const char *names_csv, const char *directory,
                        char *errbuf, int errlen);
int ec_registry_count(void);

/* ---- plugin entry points (each libec_<name>.so exports these) --------- */
/* const char *__erasure_code_version(void);
 * int __erasure_code_init(const char *plugin_name, const char *directory);
 */

#ifdef __cplusplus
}
#endif
#endif /* CEPH_TPU_EC_ABI_H */
