// sptag_tpu native host components.
//
// The reference keeps its whole runtime in C++; in the TPU-native design the
// device math lives in XLA/Pallas and the host runtime stays native where
// the reference's is performance-critical.  This library provides:
//
//  * the parallel TSV ingestion parser — parity with
//    Helper::DefaultReader's block subtasks
//    (/root/reference/AnnService/src/Helper/VectorSetReaders/
//    DefaultReader.cpp:200-320): "<meta>\t<v1>|<v2>|...\n" lines parsed
//    into a row-major float32 matrix + metadata offsets, one block per
//    thread;
//  * the wire packet-header codec (inc/Socket/Packet.h:52-76) for
//    high-throughput serving front doors;
//  * the served query-vector parser: the text vectors of one batch group
//    ("<v1>|<v2>|...", SearchExecutionContext::ExtractVector's text form)
//    parsed by one call into the group's (Q, D) array of the index's
//    value type.  It accepts a strict subset of what the Python route
//    (serve/protocol.py::ParsedQuery.extract_vector) accepts, with the
//    same value; a row it does not accept is left for that route to decide.
//
// Exposed as a plain C ABI for ctypes (no pybind11 in this toolchain).
//
// Build: g++ -O3 -march=native -shared -fPIC -o libsptag_host.so
//        sptag_host.cpp -lpthread

#include <cfloat>
#include <charconv>
#include <cmath>
#include <cstdint>
#include <cstdlib>
#include <cstring>
#include <limits>
#include <system_error>
#include <thread>
#include <type_traits>
#include <vector>

// Query-vector parse helpers (templates: outside the C linkage block).
namespace {

// One row "<v1><sep><v2>..." -> out[0..dim).  Empty elements are skipped
// (doubled / leading / trailing separators), as extract_vector's
// `[p for p in text.split(sep) if p != ""]` skips them.  An element is
// accepted only if std::from_chars (locale-free, correctly rounded: the
// value Python's float() gives) reads a number that ends exactly at the
// next separator or at the row's end, without error, and the double is
// finite and the row type can hold it: ndarray.astype's C cast is then
// defined and is this cast.  What from_chars reads is made of [0-9.eE+-]
// alone - or spells inf / nan, which are not finite - so it holds no
// separator (the caller keeps separators of that alphabet away) and is an
// element str.split would have cut the same way; Python's float() accepts
// every such element with the same value.  So a false return never
// decides a request: the caller hands the row to the Python route.
template <typename T>
bool parse_row(const char* p, const char* end, char sep, int dim, T* out) {
    int d = 0;
    while (p < end) {
        if (*p == sep) {
            ++p;
            continue;
        }
        if (d == dim) return false;
        double v = 0.0;
        const std::from_chars_result r = std::from_chars(p, end, v);
        if (r.ec != std::errc() || (r.ptr != end && *r.ptr != sep)
            || !std::isfinite(v))
            return false;
        if constexpr (std::is_floating_point<T>::value) {
            if (std::fabs(v) > static_cast<double>(FLT_MAX)) return false;
            out[d] = static_cast<T>(v);
        } else {
            // astype truncates toward zero; in range after truncation
            const double t = std::trunc(v);
            if (t < static_cast<double>(std::numeric_limits<T>::min())
                || t > static_cast<double>(std::numeric_limits<T>::max()))
                return false;
            out[d] = static_cast<T>(t);
        }
        ++d;
        p = r.ptr;
    }
    return d == dim;
}

template <typename T>
void parse_rows(const char* buf, const long long* offsets, long long rows,
                int dim, char sep, T* out, std::uint8_t* row_ok) {
    for (long long r = 0; r < rows; ++r) {
        row_ok[r] = parse_row<T>(buf + offsets[r], buf + offsets[r + 1],
                                 sep, dim, out + r * dim) ? 1 : 0;
    }
}

}  // namespace

extern "C" {

// ---------------------------------------------------------------- TSV parse

// Pass 1: count data lines (non-empty) in [buf, buf+len).
long long sptag_count_lines(const char* buf, long long len) {
    long long rows = 0;
    const char* end = buf + len;
    const char* p = buf;
    while (p < end) {
        const char* nl = static_cast<const char*>(
            memchr(p, '\n', static_cast<size_t>(end - p)));
        const char* line_end = nl ? nl : end;
        if (line_end > p && !(line_end - p == 1 && *p == '\r')) ++rows;
        p = nl ? nl + 1 : end;
    }
    return rows;
}

namespace {

struct BlockResult {
    long long rows_filled = 0;
    int dim_seen = 0;
    int error = 0;
};

// Parse one block of lines into out[row0*dim ...]; metadata copied into
// meta_buf at meta_offsets[global_row].  Caller sizes out for the counted
// rows and meta_buf for the block's byte length (metadata is never longer
// than its line).
void parse_block(const char* buf, long long len, char delim, int dim,
                 float* out, long long row0,
                 char* meta_buf, long long meta_cap,
                 long long* meta_lens, BlockResult* result) {
    const char* end = buf + len;
    const char* p = buf;
    long long row = row0;
    long long meta_used = 0;
    while (p < end) {
        const char* nl = static_cast<const char*>(
            memchr(p, '\n', static_cast<size_t>(end - p)));
        const char* line_end = nl ? nl : end;
        if (line_end > p && *(line_end - 1) == '\r') --line_end;
        if (line_end <= p) {
            p = nl ? nl + 1 : end;
            continue;
        }
        const char* tab = static_cast<const char*>(
            memchr(p, '\t', static_cast<size_t>(line_end - p)));
        const char* vec_begin = p;
        long long meta_len = 0;
        if (tab) {
            meta_len = tab - p;
            vec_begin = tab + 1;
        }
        if (meta_len > 0 && meta_used + meta_len <= meta_cap) {
            memcpy(meta_buf + meta_used, p, static_cast<size_t>(meta_len));
        }
        meta_lens[row] = meta_len;
        meta_used += meta_len;

        float* out_row = out + row * dim;
        int d = 0;
        const char* q = vec_begin;
        while (q < line_end && d < dim) {
            char* parse_end = nullptr;
            float v = strtof(q, &parse_end);
            if (parse_end == q) break;
            out_row[d++] = v;
            q = parse_end;
            if (q < line_end && *q == delim) ++q;
        }
        if (d != dim) {
            result->error = 1;
            result->dim_seen = d;
            return;
        }
        ++row;
        p = nl ? nl + 1 : end;
    }
    result->rows_filled = row - row0;
}

}  // namespace

// Parallel parse: splits [buf, len) into n_threads blocks on line
// boundaries; fills out (rows x dim float32), meta_blob (concatenated
// metadata bytes, caller-capacity len) and meta_lens (rows).  Returns rows
// parsed, or -1 on malformed input (dimension mismatch).
long long sptag_parse_tsv(const char* buf, long long len, char delim,
                          int dim, int n_threads, float* out,
                          char* meta_blob, long long* meta_lens) {
    if (len <= 0 || dim <= 0) return 0;
    if (n_threads < 1) n_threads = 1;

    // block boundaries on line starts
    std::vector<long long> bounds;
    bounds.push_back(0);
    long long step = len / n_threads;
    for (int i = 1; i < n_threads; ++i) {
        long long want = i * step;
        if (want <= bounds.back()) continue;
        const char* nl = static_cast<const char*>(
            memchr(buf + want, '\n', static_cast<size_t>(len - want)));
        if (!nl) break;
        long long pos = (nl - buf) + 1;
        if (pos > bounds.back() && pos < len) bounds.push_back(pos);
    }
    bounds.push_back(len);

    const size_t n_blocks = bounds.size() - 1;
    std::vector<long long> row_starts(n_blocks + 1, 0);
    for (size_t b = 0; b < n_blocks; ++b) {
        row_starts[b + 1] = row_starts[b]
            + sptag_count_lines(buf + bounds[b], bounds[b + 1] - bounds[b]);
    }

    std::vector<BlockResult> results(n_blocks);
    // per-block metadata staging: block b's metadata is <= its byte length
    std::vector<std::vector<char>> staging(n_blocks);
    std::vector<std::thread> threads;
    threads.reserve(n_blocks);
    for (size_t b = 0; b < n_blocks; ++b) {
        staging[b].resize(static_cast<size_t>(bounds[b + 1] - bounds[b]));
        threads.emplace_back(parse_block, buf + bounds[b],
                             bounds[b + 1] - bounds[b], delim, dim, out,
                             row_starts[b], staging[b].data(),
                             static_cast<long long>(staging[b].size()),
                             meta_lens, &results[b]);
    }
    for (auto& t : threads) t.join();
    for (size_t b = 0; b < n_blocks; ++b) {
        if (results[b].error) return -1;
    }

    // merge pass: concatenate metadata in row order
    long long total_rows = row_starts[n_blocks];
    long long off = 0;
    for (size_t b = 0; b < n_blocks; ++b) {
        long long staged = 0;
        for (long long r = row_starts[b]; r < row_starts[b + 1]; ++r) {
            memcpy(meta_blob + off, staging[b].data() + staged,
                   static_cast<size_t>(meta_lens[r]));
            off += meta_lens[r];
            staged += meta_lens[r];
        }
    }
    return total_rows;
}

// ------------------------------------------------------- query-vector parse

// Row r is the bytes buf[offsets[r] : offsets[r + 1]].  value_type is the
// reference's VectorValueType code (DefinitionList.h: Int8 0, UInt8 1,
// Int16 2, Float 3); out is (rows x dim) of that type.  row_ok[r] is 1
// only if row r had exactly dim accepted elements; the contents of a row
// that is not ok are unspecified.  sep is no character a number is written
// with ([0-9.eE+-]).  Returns 0, or -1 for such a separator or a value
// type it does not know (nothing is written).
int sptag_parse_query_vectors(const char* buf, const long long* offsets,
                              long long rows, int dim, char sep,
                              int value_type, void* out,
                              std::uint8_t* row_ok) {
    if ((sep >= '0' && sep <= '9') || std::strchr(".eE+-", sep)) return -1;
    if (rows <= 0 || dim <= 0) return 0;
    switch (value_type) {
    case 0:
        parse_rows(buf, offsets, rows, dim, sep,
                   static_cast<std::int8_t*>(out), row_ok);
        return 0;
    case 1:
        parse_rows(buf, offsets, rows, dim, sep,
                   static_cast<std::uint8_t*>(out), row_ok);
        return 0;
    case 2:
        parse_rows(buf, offsets, rows, dim, sep,
                   static_cast<std::int16_t*>(out), row_ok);
        return 0;
    case 3:
        parse_rows(buf, offsets, rows, dim, sep,
                   static_cast<float*>(out), row_ok);
        return 0;
    default:
        return -1;
    }
}

// ------------------------------------------------------------ packet codec

// 16-byte header: u8 type, u8 status, u32 bodyLength, u32 connectionID,
// u32 resourceID, 2B pad (inc/Socket/Packet.h:52-76).
void sptag_pack_header(std::uint8_t type, std::uint8_t status,
                       std::uint32_t body_length,
                       std::uint32_t connection_id,
                       std::uint32_t resource_id, std::uint8_t* out16) {
    out16[0] = type;
    out16[1] = status;
    memcpy(out16 + 2, &body_length, 4);
    memcpy(out16 + 6, &connection_id, 4);
    memcpy(out16 + 10, &resource_id, 4);
    out16[14] = 0;
    out16[15] = 0;
}

void sptag_unpack_header(const std::uint8_t* in16, std::uint8_t* type,
                         std::uint8_t* status, std::uint32_t* body_length,
                         std::uint32_t* connection_id,
                         std::uint32_t* resource_id) {
    *type = in16[0];
    *status = in16[1];
    memcpy(body_length, in16 + 2, 4);
    memcpy(connection_id, in16 + 6, 4);
    memcpy(resource_id, in16 + 10, 4);
}

}  // extern "C"
