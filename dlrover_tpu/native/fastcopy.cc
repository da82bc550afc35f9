// GIL-free bulk memcpy for the flash-checkpoint shm path.
//
// Reference capability: the reference's hot shm copy
// (_traverse_copy_to_shm, ckpt_saver.py:174) runs torch's C++ memcpy
// which drops the GIL.  numpy's copyto holds the GIL for the whole
// transfer, so a multi-GB snapshot written by the async writer thread
// starves every other thread in the trainer (heartbeats, IPC replies)
// for seconds on low-memory-bandwidth hosts.  This copies in chunks
// through a plain C ABI; the Python binding releases the GIL around
// the call (ctypes does this automatically for foreign calls).
//
// dlrover_fastcopy_strided is the same copy from a source that is not
// row-major: jax.device_get hands a leaf back in the DEVICE buffer's
// dimension order (an axis permutation of a dense buffer wherever the
// TPU keeps a weight in another order than row-major), and the shm
// segment stores every leaf row-major.  One pass, no intermediate
// dense copy: numpy's ascontiguousarray walked such a leaf element by
// element with the GIL held (1.5 GB/s against this file's 10 GB/s).

#include <cstddef>
#include <cstdint>
#include <cstring>
#include <thread>
#include <utility>
#include <vector>

namespace {

constexpr int kMaxDims = 32;  // a leaf of more axes is left to numpy
// A tile of the transposed case, in items: kTileSrc along the axis the
// source runs along, kTileDst along the destination's (the best of
// five shapes on the v5e host, 2-byte items: PERF.md, PR 27).
constexpr int64_t kTileSrc = 32;
constexpr int64_t kTileDst = 128;

// A strided source over a dense row-major destination, after size-1
// axes are dropped and axes that are adjacent in BOTH are merged.
struct View {
  int ndim = 0;
  int64_t shape[kMaxDims];
  int64_t src_stride[kMaxDims];  // bytes
  int64_t dst_stride[kMaxDims];  // bytes, row-major
};

View normalize(int ndim, const int64_t* shape, const int64_t* strides,
               int64_t item) {
  View v;
  for (int i = 0; i < ndim; ++i) {
    if (shape[i] == 1) continue;
    if (v.ndim > 0 &&
        v.src_stride[v.ndim - 1] == strides[i] * shape[i]) {
      // axis i continues the previous one in the source as it does
      // in the destination: one longer axis
      v.shape[v.ndim - 1] *= shape[i];
      v.src_stride[v.ndim - 1] = strides[i];
    } else {
      v.shape[v.ndim] = shape[i];
      v.src_stride[v.ndim] = strides[i];
      ++v.ndim;
    }
  }
  int64_t run = item;
  for (int i = v.ndim - 1; i >= 0; --i) {
    v.dst_stride[i] = run;
    run *= v.shape[i];
  }
  return v;
}

// Offsets of the `index`-th position of the axes in `axes` (row-major
// over them), in the source and in the destination.
void locate(const View& v, const int* axes, int n, int64_t index,
            int64_t* src_off, int64_t* dst_off) {
  int64_t s = 0, d = 0;
  for (int k = n - 1; k >= 0; --k) {
    const int a = axes[k];
    const int64_t i = index % v.shape[a];
    index /= v.shape[a];
    s += i * v.src_stride[a];
    d += i * v.dst_stride[a];
  }
  *src_off = s;
  *dst_off = d;
}

// Rows [lo, hi) of the destination (a row = the last axis), each read
// with the source's stride along that axis: a memcpy where the source
// runs along it too, else element by element.  Correct for any
// strides; fast only for the first kind.
template <typename T>
void copy_rows(const View& v, char* dst, const char* src, int64_t lo,
               int64_t hi) {
  const int last = v.ndim - 1;
  const int64_t n = v.shape[last], step = v.src_stride[last];
  int outer[kMaxDims];
  for (int i = 0; i < last; ++i) outer[i] = i;
  for (int64_t row = lo; row < hi; ++row) {
    int64_t s, d;
    locate(v, outer, last, row, &s, &d);
    if (step == static_cast<int64_t>(sizeof(T))) {
      std::memcpy(dst + d, src + s, n * sizeof(T));
      continue;
    }
    const char* from = src + s;
    T* to = reinterpret_cast<T*>(dst + d);
    for (int64_t i = 0; i < n; ++i, from += step) {
      std::memcpy(to + i, from, sizeof(T));
    }
  }
}

// 16 bytes of T in one register.
template <typename T>
struct Vec {
  typedef T type __attribute__((vector_size(16)));
};

// The interleave a0 b0 a1 b1 .. of the lower (Half 0) or upper (Half 1)
// halves of two vectors of K items; I is 0 .. K-1.
template <int Half, typename V, int... I>
inline V interleave(V a, V b, std::integer_sequence<int, I...>) {
  constexpr int K = sizeof...(I);
  return __builtin_shufflevector(
      a, b, ((I % 2) * K + Half * (K / 2) + I / 2)...);
}

// dst[i][j] = src[j][i] for a K x K block, K = 16 / sizeof(T), in
// registers: K rows of the source are loaded, log2(K) rounds of the
// perfect shuffle (row i with row i + K/2, interleaved) rotate the
// (row, column) index by one bit each, which after log2(K) rounds has
// swapped the two; K rows of the destination are stored.  Strides in
// bytes.
template <typename T>
inline void transpose_block(char* dst, int64_t dst_stride,
                            const char* src, int64_t src_stride) {
  constexpr int K = 16 / sizeof(T);
  constexpr std::make_integer_sequence<int, K> items{};
  typename Vec<T>::type a[K], b[K];
  for (int k = 0; k < K; ++k) std::memcpy(&a[k], src + k * src_stride, 16);
  for (int round = 1; round < K; round *= 2) {
    for (int i = 0; i < K / 2; ++i) {
      b[2 * i] = interleave<0>(a[i], a[i + K / 2], items);
      b[2 * i + 1] = interleave<1>(a[i], a[i + K / 2], items);
    }
    for (int k = 0; k < K; ++k) a[k] = b[k];
  }
  for (int k = 0; k < K; ++k) std::memcpy(dst + k * dst_stride, &a[k], 16);
}

// The transposed case: the source runs along axis `a`, the
// destination along the last axis.  Work units [lo, hi) are
// (position of the other axes) x (tile of axis a).  A tile is
// kTileSrc x kTileDst items, small enough to stay in the first-level
// cache, so every line fetched from the source and every line of the
// destination is used whole; inside it, K x K blocks go through
// registers and the edges item by item.
template <typename T>
void copy_tiles(const View& v, int a, char* dst, const char* src,
                int64_t lo, int64_t hi) {
  constexpr int64_t K = 16 / sizeof(T), item = sizeof(T);
  const int last = v.ndim - 1;
  const int64_t na = v.shape[a], nl = v.shape[last];
  const int64_t a_tiles = (na + kTileSrc - 1) / kTileSrc;
  const int64_t src_l = v.src_stride[last];
  const int64_t dst_a = v.dst_stride[a];
  int outer[kMaxDims];
  int n_outer = 0;
  for (int i = 0; i < last; ++i) {
    if (i != a) outer[n_outer++] = i;
  }
  auto one = [&](const char* s, char* d, int64_t i, int64_t j) {
    std::memcpy(d + i * dst_a + j * item, s + i * item + j * src_l, item);
  };
  for (int64_t unit = lo; unit < hi; ++unit) {
    int64_t s_off, d_off;
    locate(v, outer, n_outer, unit / a_tiles, &s_off, &d_off);
    const char* s = src + s_off;
    char* d = dst + d_off;
    const int64_t a0 = (unit % a_tiles) * kTileSrc;
    const int64_t a1 = a0 + kTileSrc < na ? a0 + kTileSrc : na;
    for (int64_t l0 = 0; l0 < nl; l0 += kTileDst) {
      const int64_t l1 = l0 + kTileDst < nl ? l0 + kTileDst : nl;
      int64_t i = a0;
      for (; i + K <= a1; i += K) {
        int64_t j = l0;
        for (; j + K <= l1; j += K) {
          transpose_block<T>(d + i * dst_a + j * item, dst_a,
                             s + i * item + j * src_l, src_l);
        }
        for (; j < l1; ++j) {
          for (int64_t k = i; k < i + K; ++k) one(s, d, k, j);
        }
      }
      for (; i < a1; ++i) {
        for (int64_t j = l0; j < l1; ++j) one(s, d, i, j);
      }
    }
  }
}

template <typename T>
void copy_view(const View& v, char* dst, const char* src, size_t total,
               int threads) {
  const int last = v.ndim - 1;
  // the axis the source runs along, if it is not the destination's
  int a = -1;
  if (v.src_stride[last] != static_cast<int64_t>(sizeof(T))) {
    for (int i = 0; i < last; ++i) {
      if (v.src_stride[i] == static_cast<int64_t>(sizeof(T))) a = i;
    }
  }
  int64_t units = 1;
  for (int i = 0; i < last; ++i) {
    units *= (i == a) ? (v.shape[i] + kTileSrc - 1) / kTileSrc : v.shape[i];
  }
  auto part = [&](int64_t lo, int64_t hi) {
    if (a >= 0) {
      copy_tiles<T>(v, a, dst, src, lo, hi);
    } else {
      copy_rows<T>(v, dst, src, lo, hi);
    }
  };
  // a thread for each MiB at most: starting one costs more than
  // copying a small leaf
  int64_t n = static_cast<int64_t>(total >> 20);
  if (n > threads) n = threads;
  if (n > units) n = units;
  if (n <= 1) {
    part(0, units);
    return;
  }
  std::vector<std::thread> pool;
  for (int64_t t = 1; t < n; ++t) {
    pool.emplace_back(part, units * t / n, units * (t + 1) / n);
  }
  part(0, units / n);
  for (auto& th : pool) th.join();
}

}  // namespace

extern "C" {

// Copy n bytes from src to dst.  Returns n.
size_t dlrover_fastcopy(void* dst, const void* src, size_t n) {
  std::memcpy(dst, src, n);
  return n;
}

// Copy a strided source (shape, strides in BYTES, item size 1, 2, 4 or
// 8) into a dense row-major destination of the same shape, over up to
// `threads` threads.  Returns the bytes written, 0 for an item size or
// a rank it does not take (the caller falls back to numpy).
size_t dlrover_fastcopy_strided(void* dst, const void* src, int ndim,
                                const int64_t* shape,
                                const int64_t* strides, size_t item,
                                int threads) {
  if (ndim < 0 || ndim > kMaxDims) return 0;
  if (item != 1 && item != 2 && item != 4 && item != 8) return 0;
  size_t total = item;
  for (int i = 0; i < ndim; ++i) total *= static_cast<size_t>(shape[i]);
  if (total == 0) return 0;
  View v = normalize(ndim, shape, strides, static_cast<int64_t>(item));
  if (v.ndim == 0) {  // one element
    std::memcpy(dst, src, item);
    return item;
  }
  char* d = static_cast<char*>(dst);
  const char* s = static_cast<const char*>(src);
  switch (item) {
    case 1: copy_view<uint8_t>(v, d, s, total, threads); break;
    case 2: copy_view<uint16_t>(v, d, s, total, threads); break;
    case 4: copy_view<uint32_t>(v, d, s, total, threads); break;
    default: copy_view<uint64_t>(v, d, s, total, threads); break;
  }
  return total;
}

}  // extern "C"
