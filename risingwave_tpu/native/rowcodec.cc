// Native host kernels for the state-persistence hot path.
//
// The reference implements row serde / hashing in Rust (src/common/src/
// row/, util/memcmp_encoding.rs, hash/); the TPU build keeps device
// compute in XLA and gives the HOST runtime the same native treatment:
// the crc32 vnode hash, and the SST record packer / unpacker for fixed-width
// sorted runs, each over whole batches instead of per-row Python. (Batch
// key and value encoding is numpy's: state/serde.py BatchCodec.) Byte
// formats are bit-identical to common/vnode.py and state/sstable.py
// (golden-tested from tests/test_native.py).

#include <cstdint>
#include <cstring>

extern "C" {

// crc32 (poly 0xEDB88320) over the LE bytes of k int64 columns per row,
// column-major in argument order — bit-identical to vnode.crc32_numpy
void crc32_i64_cols(const int64_t* vals /* n*k row-major */, int64_t n,
                    int64_t k, uint32_t* out) {
    static uint32_t table[256];
    static bool init = false;
    if (!init) {
        for (uint32_t i = 0; i < 256; ++i) {
            uint32_t c = i;
            for (int j = 0; j < 8; ++j)
                c = (c & 1) ? (c >> 1) ^ 0xEDB88320u : c >> 1;
            table[i] = c;
        }
        init = true;
    }
    for (int64_t r = 0; r < n; ++r) {
        uint32_t crc = 0xFFFFFFFFu;
        for (int64_t c = 0; c < k; ++c) {
            uint64_t u = (uint64_t)vals[r * k + c];
            for (int b = 0; b < 8; ++b) {
                uint32_t byte = (uint32_t)((u >> (8 * b)) & 0xFF);
                crc = (crc >> 8) ^ table[(crc ^ byte) & 0xFF];
            }
        }
        out[r] = crc ^ 0xFFFFFFFFu;
    }
}

// SST records (state/sstable.py, format RWS1) of one sorted run whose keys
// are all K bytes and whose put values are all V bytes wide:
//   put:       u32le K | key | u32le V | value
//   tombstone: u32le K | key | u32le 0xFFFFFFFF
// Returns the bytes written (n * (8 + K) + puts * V).
int64_t sst_pack_fixed(const uint8_t* keys, const uint8_t* vals,
                       const uint8_t* put, int64_t n, int64_t K, int64_t V,
                       uint8_t* out) {
    const uint32_t klen = (uint32_t)K, vlen = (uint32_t)V;
    const uint32_t tomb = 0xFFFFFFFFu;
    uint8_t* p = out;
    for (int64_t r = 0; r < n; ++r) {
        std::memcpy(p, &klen, 4);
        std::memcpy(p + 4, keys + r * K, (size_t)K);
        p += 4 + K;
        if (put[r]) {
            std::memcpy(p, &vlen, 4);
            std::memcpy(p + 4, vals + r * V, (size_t)V);
            p += 4 + V;
        } else {
            std::memcpy(p, &tomb, 4);
            p += 4;
        }
    }
    return (int64_t)(p - out);
}

// Walk the `count` records of an SST body laid out as above from `off`:
// per record the offset and length of its key and its value length
// (0xFFFFFFFF = tombstone). Returns the offset after the last record, -1
// if a record runs past `size`.
int64_t sst_index(const uint8_t* body, int64_t size, int64_t off,
                  int64_t count, int64_t* koff, uint32_t* klen,
                  uint32_t* vlen) {
    for (int64_t r = 0; r < count; ++r) {
        if (off + 4 > size) return -1;
        std::memcpy(&klen[r], body + off, 4);
        koff[r] = off + 4;
        off += 4 + (int64_t)klen[r];
        if (off + 4 > size) return -1;
        std::memcpy(&vlen[r], body + off, 4);
        off += 4;
        if (vlen[r] != 0xFFFFFFFFu) off += (int64_t)vlen[r];
        if (off > size) return -1;
    }
    return off;
}

// The inverse of sst_pack_fixed for n records that sst_index found to be
// K / V wide: keys and put values copied out into the matrices (a
// tombstone's value row is zeroed).
void sst_unpack_fixed(const uint8_t* body, const int64_t* koff,
                      const uint8_t* put, int64_t n, int64_t K, int64_t V,
                      uint8_t* keys, uint8_t* vals) {
    for (int64_t r = 0; r < n; ++r) {
        const uint8_t* p = body + koff[r];
        std::memcpy(keys + r * K, p, (size_t)K);
        if (put[r]) std::memcpy(vals + r * V, p + K + 4, (size_t)V);
        else std::memset(vals + r * V, 0, (size_t)V);
    }
}

}  // extern "C"
