// Native data-pipeline helpers for megatron_tpu.
//
// TPU-native equivalent of the reference's pybind11 CPU extension
// (ref: megatron/data/helpers.cpp — build_sample_idx :83-166,
// build_blending_indices :20-80). Same algorithms, re-expressed as a plain
// extern "C" shared library consumed through ctypes (pybind11 is not in this
// image). Compiled on demand by megatron_tpu/data/helpers.py.
//
// Build: helpers.py does it on first use (g++ -O3 -shared -fPIC), into
// _helpers_<sha256 of this file>.so

#include <cstdint>

extern "C" {

// Sequential sample-index walk. `sizes`: tokens per sequence in the indexed
// dataset; `doc_idx`: epoch-replicated shuffled document ids; out:
// [num_samples+1, 2] int32 of (doc_idx position, in-doc token offset).
// Mirrors the -1 one-token-overlap bookkeeping of the reference walk.
void build_sample_idx(const int32_t* sizes, const int32_t* doc_idx,
                      int64_t doc_idx_len, int32_t seq_length,
                      int32_t num_epochs, int64_t tokens_per_epoch,
                      int32_t* out /* [(num_samples+1)*2] */) {
    const int64_t num_samples =
        (static_cast<int64_t>(num_epochs) * tokens_per_epoch - 1) / seq_length;

    int64_t sample_index = 0;
    int64_t doc_idx_index = 0;
    int32_t doc_offset = 0;

    out[0] = static_cast<int32_t>(doc_idx_index);
    out[1] = doc_offset;
    ++sample_index;

    while (sample_index <= num_samples) {
        int32_t remaining = seq_length + 1;
        while (remaining != 0) {
            const int32_t doc_id = doc_idx[doc_idx_index];
            const int32_t doc_length = sizes[doc_id] - doc_offset;
            remaining -= doc_length;
            if (remaining <= 0) {
                doc_offset += remaining + doc_length - 1;
                remaining = 0;
            } else {
                if (doc_idx_index + 1 >= doc_idx_len) {
                    // stream exhausted (can only happen on the final +1
                    // sentinel entry); clamp at the end
                    doc_offset = sizes[doc_id];
                    remaining = 0;
                } else {
                    ++doc_idx_index;
                    doc_offset = 0;
                }
            }
        }
        out[2 * sample_index] = static_cast<int32_t>(doc_idx_index);
        out[2 * sample_index + 1] = doc_offset;
        ++sample_index;
    }
}

// Greedy weight-balancing blend: for each output position pick the dataset
// whose emitted count is furthest behind weight * position.
void build_blending_indices(const double* weights, int32_t num_datasets,
                            int64_t size, uint8_t* dataset_index,
                            int64_t* dataset_sample_index) {
    int64_t current[256] = {0};
    for (int64_t i = 0; i < size; ++i) {
        double max_error = -1e300;
        int32_t best = 0;
        for (int32_t d = 0; d < num_datasets; ++d) {
            const double error =
                weights[d] * static_cast<double>(i + 1) -
                static_cast<double>(current[d]);
            if (error > max_error) {
                max_error = error;
                best = d;
            }
        }
        dataset_index[i] = static_cast<uint8_t>(best);
        dataset_sample_index[i] = current[best];
        ++current[best];
    }
}

}  // extern "C"

// ---------------------------------------------------------------------------
// Sentence-pair / block mappings for BERT-style and ICT/REALM datasets.
//
// Contract of the reference's build_mapping / build_blocks_mapping
// (ref: megatron/data/helpers.cpp:188-670): walk documents of sentences,
// cut them into samples of ~target length, record (start sentence, end
// sentence, extra) triples/quads, then Fisher-Yates shuffle with
// mt19937_64(seed+1). Sample-length randomness uses mt19937(seed) with the
// same ratio trick, so maps are bit-identical to the reference's for the
// same inputs. Exposed through extern "C" in two-call form: pass
// out == nullptr to size the map, then call again to fill + shuffle.
// ---------------------------------------------------------------------------

#include <cmath>
#include <random>

namespace {

const int32_t kLongSentenceLen = 512;

inline int32_t target_sample_len(int32_t short_seq_ratio, int32_t max_length,
                                 std::mt19937& gen) {
    if (short_seq_ratio == 0) return max_length;
    const uint32_t r = gen();
    if (r % short_seq_ratio == 0) return 2 + r % (max_length - 1);
    return max_length;
}

inline void shuffle_rows(int64_t* maps, int64_t n, int width, int32_t seed) {
    std::mt19937_64 gen(seed + 1);
    for (int64_t i = n - 1; i > 0; --i) {
        const int64_t j = static_cast<int64_t>(gen() % (i + 1));
        for (int c = 0; c < width; ++c) {
            const int64_t t = maps[width * i + c];
            maps[width * i + c] = maps[width * j + c];
            maps[width * j + c] = t;
        }
    }
}

}  // namespace

extern "C" {

// Sentence-pair mapping (ref: helpers.cpp:188-420 build_mapping_impl).
// docs: [n_docs+1] sentence-index offsets; sizes: tokens per sentence.
// Returns the sample count; when out != nullptr also fills out[n*3] with
// (start sentence, end sentence (exclusive), target seq length) rows and
// shuffles them.
int64_t build_mapping(const int64_t* docs, int64_t n_docs,
                      const int32_t* sizes,
                      int32_t num_epochs, uint64_t max_num_samples,
                      int32_t max_seq_length, double short_seq_prob,
                      int32_t seed, int32_t min_num_sent,
                      int64_t* out) {
    int32_t short_seq_ratio = 0;
    if (short_seq_prob > 0)
        short_seq_ratio =
            static_cast<int32_t>(lround(1.0 / short_seq_prob));

    std::mt19937 gen(seed);
    uint64_t map_index = 0;
    for (int32_t epoch = 0; epoch < num_epochs; ++epoch) {
        if (map_index >= max_num_samples) break;
        for (int64_t doc = 0; doc < n_docs; ++doc) {
            const int64_t first = docs[doc];
            const int64_t last = docs[doc + 1];
            int64_t prev_start = first;
            int64_t remain = last - first;

            bool has_long = false;
            if (remain > 1) {
                for (int64_t s = first; s < last; ++s) {
                    if (sizes[s] > kLongSentenceLen) { has_long = true; break; }
                }
            }
            if (remain < min_num_sent || has_long) continue;

            int32_t seq_len = 0;
            int32_t num_sent = 0;
            int32_t target = target_sample_len(short_seq_ratio,
                                               max_seq_length, gen);
            for (int64_t s = first; s < last; ++s) {
                seq_len += sizes[s];
                ++num_sent;
                --remain;
                if ((seq_len >= target && remain > 1 &&
                     num_sent >= min_num_sent) || remain == 0) {
                    if (out != nullptr) {
                        out[3 * map_index] = prev_start;
                        out[3 * map_index + 1] = s + 1;
                        out[3 * map_index + 2] = target;
                    }
                    ++map_index;
                    prev_start = s + 1;
                    target = target_sample_len(short_seq_ratio,
                                               max_seq_length, gen);
                    seq_len = 0;
                    num_sent = 0;
                }
            }
        }
    }
    if (out != nullptr)
        shuffle_rows(out, static_cast<int64_t>(map_index), 3, seed);
    return static_cast<int64_t>(map_index);
}

// ICT/REALM block mapping (ref: helpers.cpp:453-670
// build_blocks_mapping_impl). Rows are (start sentence, end sentence,
// document index, block id); target length shrinks by the document's title
// size so title + block fit max_seq_length together.
int64_t build_blocks_mapping(const int64_t* docs, int64_t n_docs,
                             const int32_t* sizes,
                             const int32_t* titles_sizes,
                             int32_t num_epochs, uint64_t max_num_samples,
                             int32_t max_seq_length, int32_t seed,
                             int32_t use_one_sent_blocks,
                             int64_t* out) {
    const int32_t min_num_sent = use_one_sent_blocks ? 1 : 2;
    uint64_t map_index = 0;
    for (int32_t epoch = 0; epoch < num_epochs; ++epoch) {
        int64_t block_id = 0;
        if (map_index >= max_num_samples) break;
        for (int64_t doc = 0; doc < n_docs; ++doc) {
            const int64_t first = docs[doc];
            const int64_t last = docs[doc + 1];
            const int32_t target = max_seq_length - titles_sizes[doc];
            int64_t prev_start = first;
            int64_t remain = last - first;

            bool has_long = false;
            if (remain >= min_num_sent) {
                for (int64_t s = first; s < last; ++s) {
                    if (sizes[s] > kLongSentenceLen) { has_long = true; break; }
                }
            }
            if (remain < min_num_sent || has_long) continue;

            int32_t seq_len = 0;
            int32_t num_sent = 0;
            for (int64_t s = first; s < last; ++s) {
                seq_len += sizes[s];
                ++num_sent;
                --remain;
                if ((seq_len >= target && remain >= min_num_sent &&
                     num_sent >= min_num_sent) || remain == 0) {
                    if (out != nullptr) {
                        out[4 * map_index] = prev_start;
                        out[4 * map_index + 1] = s + 1;
                        out[4 * map_index + 2] = doc;
                        out[4 * map_index + 3] = block_id;
                    }
                    ++map_index;
                    ++block_id;
                    prev_start = s + 1;
                    seq_len = 0;
                    num_sent = 0;
                }
            }
        }
    }
    if (out != nullptr)
        shuffle_rows(out, static_cast<int64_t>(map_index), 4, seed);
    return static_cast<int64_t>(map_index);
}

}  // extern "C"
