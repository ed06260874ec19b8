// Native host-side text kernels: token hashing and fused tokenize+hash.
//
// The TPU build's equivalent of the reference's JVM text machinery (Lucene
// analyzers + Spark HashingTF running on executors — reference:
// core/.../impl/feature/TextTokenizer.scala, OPCollectionHashingVectorizer.scala,
// SmartTextVectorizer.scala). Strings never belong on the TPU: the hashing
// trick runs on the host, and this library keeps that path at C speed while
// the resulting count matrices go to the device for the MXU work.
//
// Parity contract with the Python fallback (impl/feature/vectorizers.py):
// - hashes are zlib crc32 over the token's UTF-8 bytes, mod num_hashes
//   (bit-identical: we link the same zlib);
// - tokenize_hash_count reproduces tokenize_text() for pure-ASCII docs
//   (lowercase, split on non-[A-Za-z0-9_], min token length) and flags
//   non-ASCII docs for the caller to handle with the Python tokenizer
//   (Python \w is unicode-aware; we do not re-implement Unicode here).
//
// Built by utils/text_native.py on first use (g++ -O2 -shared -lz), cached
// in native/_build/; everything degrades to the numpy/Python implementation
// when no toolchain is present.

#include <cstdint>
#include <cstring>
#include <zlib.h>

extern "C" {

// Hash pre-tokenized tokens into per-document count rows.
// buf: concatenated UTF-8 bytes of every token; tok_offs: (n_toks+1) byte
// offsets; doc_starts: (n_docs+1) token index boundaries per document.
// out: (n_docs * num_hashes) float32, zero-initialized by the caller.
void tg_hash_tokens(const char* buf, const int64_t* tok_offs, int64_t n_toks,
                    const int64_t* doc_starts, int64_t n_docs,
                    int32_t num_hashes, int32_t binary, float* out) {
    (void)n_toks;
    for (int64_t d = 0; d < n_docs; ++d) {
        float* row = out + d * num_hashes;
        for (int64_t t = doc_starts[d]; t < doc_starts[d + 1]; ++t) {
            const unsigned char* p =
                reinterpret_cast<const unsigned char*>(buf + tok_offs[t]);
            const int64_t len = tok_offs[t + 1] - tok_offs[t];
            const uint32_t h =
                static_cast<uint32_t>(crc32(0L, p, static_cast<uInt>(len)));
            row[h % static_cast<uint32_t>(num_hashes)] += 1.0f;
        }
        if (binary) {
            for (int32_t j = 0; j < num_hashes; ++j)
                if (row[j] > 1.0f) row[j] = 1.0f;
        }
    }
}

static inline bool word_byte(unsigned char c) {
    return (c >= 'a' && c <= 'z') || (c >= 'A' && c <= 'Z') ||
           (c >= '0' && c <= '9') || c == '_';
}

// Fused tokenize(lowercase, split on non-word) + crc32 hash + count for
// packed documents. Non-ASCII documents are skipped with needs_py[d]=1 so
// the caller can run the Unicode-aware Python tokenizer on just those rows.
// buf: concatenated doc bytes; offs: (n_docs+1) byte offsets. Returns the
// tokens it counted (those of the documents it kept).
int64_t tg_tokenize_hash_count(const char* buf, const int64_t* offs,
                            int64_t n_docs, int32_t num_hashes,
                            int32_t min_token_len, int32_t binary,
                            float* out, uint8_t* needs_py) {
    unsigned char tok[4096];
    int64_t n_tokens = 0;
    for (int64_t d = 0; d < n_docs; ++d) {
        const unsigned char* p =
            reinterpret_cast<const unsigned char*>(buf + offs[d]);
        const int64_t len = offs[d + 1] - offs[d];
        bool ascii = true;
        for (int64_t i = 0; i < len; ++i) {
            if (p[i] >= 0x80) { ascii = false; break; }
        }
        if (!ascii) { needs_py[d] = 1; continue; }
        needs_py[d] = 0;
        float* row = out + d * num_hashes;
        int64_t i = 0;
        int64_t doc_tokens = 0;
        while (i < len) {
            while (i < len && !word_byte(p[i])) ++i;
            int64_t tl = 0;
            while (i < len && word_byte(p[i])) {
                unsigned char c = p[i];
                if (c >= 'A' && c <= 'Z') c = static_cast<unsigned char>(c + 32);
                if (tl < static_cast<int64_t>(sizeof(tok))) tok[tl] = c;
                ++tl;
                ++i;
            }
            if (tl > static_cast<int64_t>(sizeof(tok))) {
                // pathological >4 KB token: punt the whole doc to Python
                // rather than hash a truncation
                std::memset(row, 0, sizeof(float) * num_hashes);
                needs_py[d] = 1;
                break;
            }
            if (tl >= min_token_len) {
                const uint32_t h = static_cast<uint32_t>(
                    crc32(0L, tok, static_cast<uInt>(tl)));
                row[h % static_cast<uint32_t>(num_hashes)] += 1.0f;
                ++doc_tokens;
            }
        }
        if (needs_py[d]) continue;
        n_tokens += doc_tokens;
        if (binary) {
            for (int32_t j = 0; j < num_hashes; ++j)
                if (row[j] > 1.0f) row[j] = 1.0f;
        }
    }
    return n_tokens;
}

}  // extern "C"
