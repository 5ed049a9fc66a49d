// Native host dataplane: JPEG/PNG decode → crop/resize → flip → normalize,
// multithreaded, one call per batch.
//
// This is the TPU framework's native-code replacement for the reference's
// input pipeline hot path — `DataLoader(num_workers=4, pin_memory=True)`
// worker processes running PIL + torchvision transforms per sample
// (reference BASELINE/main.py:58-76,130-131). One C call fills a whole
// NHWC batch buffer in the wire's own type, so jax can ship it to device
// without further host-side work: `dp_load_batch` writes normalized float32,
// `dp_load_batch_u8` the quantized 0..255 uint8 pixels the jitted step
// normalizes on device (one resample kernel, two stores).
// Decoding dispatches on file magic bytes to libjpeg or
// libpng (PIL `convert("RGB")` semantics: palette/gray expanded, alpha
// dropped); crops follow torchvision semantics (RandomResizedCrop(scale,
// ratio 3/4..4/3, 10 tries, fallback center; val: resize-short-side +
// center crop) so training recipes match the reference's augmentation
// distribution.
//
// Build (data/native.py::_CXX does it on first use, keyed by a hash of this
// file and the flags):
//   g++ -O3 -std=c++17 -shared -fPIC -o libdataplane.so dataplane.cpp -ljpeg -lpng -lpthread

#include <algorithm>
#include <atomic>
#include <cmath>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <thread>
#include <vector>

#include <jpeglib.h>
#ifndef DP_NO_PNG
#include <png.h>
#endif
#include <csetjmp>

namespace {

// --------------------------------------------------------------- RNG -------
// SplitMix64 → xoshiro-like per-item stream; deterministic given (seed, item).
struct Rng {
  uint64_t s;
  explicit Rng(uint64_t seed) : s(seed) {}
  uint64_t next_u64() {
    s += 0x9E3779B97f4A7C15ULL;
    uint64_t z = s;
    z = (z ^ (z >> 30)) * 0xBF58476D1CE4E5B9ULL;
    z = (z ^ (z >> 27)) * 0x94D049BB133111EBULL;
    return z ^ (z >> 31);
  }
  double uniform() {  // [0, 1)
    return (next_u64() >> 11) * (1.0 / 9007199254740992.0);
  }
  double uniform(double lo, double hi) { return lo + (hi - lo) * uniform(); }
  int randint(int n) { return (int)(uniform() * n); }  // [0, n)
};

// ------------------------------------------------------------- decode ------
// Header-declared dimensions are attacker-/corruption-controlled; cap them
// before any allocation so a bogus header cannot drive out.resize() into
// std::bad_alloc (training images are far below these bounds).
constexpr int kMaxDim = 32768;
constexpr long long kMaxPixels = 64LL * 1024 * 1024;  // 192 MB RGB

bool dims_ok(int w, int h) {
  return w > 0 && h > 0 && w <= kMaxDim && h <= kMaxDim &&
         (long long)w * h <= kMaxPixels;
}

struct JpegErr {
  jpeg_error_mgr mgr;
  jmp_buf jb;
};

void jpeg_err_exit(j_common_ptr cinfo) {
  JpegErr* err = reinterpret_cast<JpegErr*>(cinfo->err);
  longjmp(err->jb, 1);
}

// Decode a JPEG file to RGB u8. Returns true on success.
bool decode_jpeg(const char* path, std::vector<uint8_t>& out, int& w, int& h) {
  FILE* f = fopen(path, "rb");
  if (!f) return false;
  jpeg_decompress_struct cinfo;
  JpegErr jerr;
  cinfo.err = jpeg_std_error(&jerr.mgr);
  jerr.mgr.error_exit = jpeg_err_exit;
  if (setjmp(jerr.jb)) {
    jpeg_destroy_decompress(&cinfo);
    fclose(f);
    return false;
  }
  jpeg_create_decompress(&cinfo);
  jpeg_stdio_src(&cinfo, f);
  jpeg_read_header(&cinfo, TRUE);
  cinfo.out_color_space = JCS_RGB;
  jpeg_start_decompress(&cinfo);
  w = cinfo.output_width;
  h = cinfo.output_height;
  if (!dims_ok(w, h)) {
    jpeg_destroy_decompress(&cinfo);
    fclose(f);
    return false;
  }
  out.resize((size_t)w * h * 3);
  while (cinfo.output_scanline < cinfo.output_height) {
    uint8_t* row = out.data() + (size_t)cinfo.output_scanline * w * 3;
    jpeg_read_scanlines(&cinfo, &row, 1);
  }
  jpeg_finish_decompress(&cinfo);
  jpeg_destroy_decompress(&cinfo);
  fclose(f);
  return true;
}

#ifndef DP_NO_PNG
// Decode a PNG file to RGB u8 via libpng. PIL-convert("RGB") semantics:
// 16-bit → 8-bit, palette/gray expanded to RGB, alpha channel dropped
// (not composited — PIL's convert discards it too). Interlaced images are
// handled by libpng itself. Returns true on success.
bool decode_png(FILE* f, std::vector<uint8_t>& out, int& w, int& h) {
  png_structp png =
      png_create_read_struct(PNG_LIBPNG_VER_STRING, nullptr, nullptr, nullptr);
  if (!png) return false;
  png_infop info = png_create_info_struct(png);
  if (!info) {
    png_destroy_read_struct(&png, nullptr, nullptr);
    return false;
  }
  if (setjmp(png_jmpbuf(png))) {
    png_destroy_read_struct(&png, &info, nullptr);
    return false;
  }
  png_init_io(png, f);
  png_read_info(png, info);
  png_set_strip_16(png);
  png_set_packing(png);
  png_set_palette_to_rgb(png);
  png_set_expand_gray_1_2_4_to_8(png);
  png_set_gray_to_rgb(png);
  png_set_strip_alpha(png);
  int passes = png_set_interlace_handling(png);
  png_read_update_info(png, info);
  w = (int)png_get_image_width(png, info);
  h = (int)png_get_image_height(png, info);
  if (!dims_ok(w, h)) {
    png_destroy_read_struct(&png, &info, nullptr);
    return false;
  }
  if (png_get_rowbytes(png, info) != (size_t)w * 3) {
    png_destroy_read_struct(&png, &info, nullptr);
    return false;  // transform chain failed to land on tight RGB rows
  }
  out.resize((size_t)w * h * 3);
  // Row-by-row into the caller's buffer: no local non-trivial object lives
  // across the setjmp/longjmp error path (a vector constructed after setjmp
  // would have its destructor skipped by a corrupt-file longjmp — per-file
  // leak); `out` belongs to the caller, so its cleanup is never skipped.
  for (int p = 0; p < passes; ++p)
    for (int y = 0; y < h; ++y)
      png_read_row(png, out.data() + (size_t)y * w * 3, nullptr);
  png_destroy_read_struct(&png, &info, nullptr);
  return true;
}

#endif  // DP_NO_PNG

// Decode a JPEG or PNG file to RGB u8, dispatching on magic bytes.
// (Built with -DDP_NO_PNG when libpng is absent: JPEG-only, PNGs fall
// through to the caller's PIL retry path.)
bool decode_image(const char* path, std::vector<uint8_t>& out, int& w, int& h) {
  FILE* f = fopen(path, "rb");
  if (!f) return false;
  uint8_t magic[8] = {0};
  size_t got = fread(magic, 1, sizeof(magic), f);
  rewind(f);
#ifndef DP_NO_PNG
  if (got >= 8 && png_sig_cmp(magic, 0, 8) == 0) {
    bool ok = decode_png(f, out, w, h);
    fclose(f);
    return ok;
  }
#endif
  fclose(f);
  if (got >= 2 && magic[0] == 0xFF && magic[1] == 0xD8)
    return decode_jpeg(path, out, w, h);
  return false;
}

// ------------------------------------------------------------ resample -----
// The two stores of the one resample kernel: what becomes of a bilinear
// sample v (0..255, double) of channel c.

// float32 wire: (v/255 - mean)/std.
struct StoreF32 {
  using type = float;
  const float* mean;
  const float* stdv;
  float operator()(double v, int c) const {
    return ((float)(v / 255.0) - mean[c]) / stdv[c];
  }
};

// uint8 wire: the same float expression under the identity pair mean 0,
// std (float)(1/255) — the float32 store's output in raw pixel units —
// rounded half-to-even in the default rounding mode (= np.rint) and clamped
// to a byte: byte for byte `clip(rint(x), 0, 255)` of the float32 batch
// under that pair (tests/test_native_dataplane.py holds it to that).
struct StoreU8 {
  using type = uint8_t;
  uint8_t operator()(double v, int) const {
    const float inv = (float)(1.0 / 255.0);
    float r = std::nearbyint(((float)(v / 255.0) - 0.0f) / inv);
    return (uint8_t)std::min(std::max(r, 0.0f), 255.0f);
  }
};

// One output column's source taps: byte offsets of the two clamped source
// columns inside a row, and the weight of the second.
struct ColTap {
  int lo, hi;
  double w;
};

// Bilinear sample from src (h×w RGB u8) region [y0,y0+ch)×[x0,x0+cw)
// scaled to out_h×out_w, optional horizontal flip, stored NHWC through
// `store`. Column taps are computed once per image (into `taps`, the
// worker's scratch), row taps once per row; the sample itself is evaluated
// in double.
template <class Store>
void crop_resize_store(const uint8_t* src, int w, int h,
                       double x0, double y0, double cw, double ch,
                       typename Store::type* dst, int out_w, int out_h,
                       bool flip, std::vector<ColTap>& taps,
                       const Store& store) {
  const double sx = cw / out_w, sy = ch / out_h;
  taps.resize(out_w);
  for (int ox = 0; ox < out_w; ++ox) {
    // torchvision/PIL bilinear: sample at pixel centers
    double fx = x0 + (ox + 0.5) * sx - 0.5;
    int x_lo = (int)std::floor(fx);
    taps[ox].lo = std::clamp(x_lo, 0, w - 1) * 3;
    taps[ox].hi = std::clamp(x_lo + 1, 0, w - 1) * 3;
    taps[ox].w = fx - x_lo;
  }
  for (int oy = 0; oy < out_h; ++oy) {
    double fy = y0 + (oy + 0.5) * sy - 0.5;
    int y_lo = (int)std::floor(fy);
    double wy = fy - y_lo;
    const uint8_t* row0 = src + (size_t)std::clamp(y_lo, 0, h - 1) * w * 3;
    const uint8_t* row1 = src + (size_t)std::clamp(y_lo + 1, 0, h - 1) * w * 3;
    typename Store::type* out_row = dst + (size_t)oy * out_w * 3;
    for (int ox = 0; ox < out_w; ++ox) {
      const ColTap& t = taps[ox];
      const double wx = t.w;
      const uint8_t* p00 = row0 + t.lo;
      const uint8_t* p01 = row0 + t.hi;
      const uint8_t* p10 = row1 + t.lo;
      const uint8_t* p11 = row1 + t.hi;
      typename Store::type* q = out_row + (flip ? (out_w - 1 - ox) : ox) * 3;
      for (int c = 0; c < 3; ++c) {
        double v = (1 - wy) * ((1 - wx) * p00[c] + wx * p01[c]) +
                   wy * ((1 - wx) * p10[c] + wx * p11[c]);
        q[c] = store(v, c);
      }
    }
  }
}

// torchvision RandomResizedCrop box: sample area∈scale·A, ratio∈(3/4,4/3),
// 10 attempts, else center fallback.
void rrc_box(Rng& rng, int w, int h, double smin, double smax,
             double& x0, double& y0, double& cw, double& ch) {
  const double area = (double)w * h;
  const double log_rmin = std::log(3.0 / 4.0), log_rmax = std::log(4.0 / 3.0);
  for (int i = 0; i < 10; ++i) {
    double target = area * rng.uniform(smin, smax);
    double ratio = std::exp(rng.uniform(log_rmin, log_rmax));
    int tw = (int)std::lround(std::sqrt(target * ratio));
    int th = (int)std::lround(std::sqrt(target / ratio));
    if (tw > 0 && th > 0 && tw <= w && th <= h) {
      x0 = rng.randint(w - tw + 1);
      y0 = rng.randint(h - th + 1);
      cw = tw;
      ch = th;
      return;
    }
  }
  // fallback: clamp ratio, center crop (torchvision semantics)
  double in_ratio = (double)w / h;
  if (in_ratio < 3.0 / 4.0) {
    cw = w;
    ch = std::round(cw / (3.0 / 4.0));
  } else if (in_ratio > 4.0 / 3.0) {
    ch = h;
    cw = std::round(ch * (4.0 / 3.0));
  } else {
    cw = w;
    ch = h;
  }
  x0 = (w - cw) / 2.0;
  y0 = (h - ch) / 2.0;
}

struct BatchJob {
  const char** paths;
  int n;
  int out_h, out_w;
  int train;
  int resize_short;
  double scale_min, scale_max;
  uint64_t seed;
  std::atomic<int> next{0};
  std::atomic<int> errors{0};
};

template <class Store>
void worker(BatchJob* job, typename Store::type* out, Store store) {
  std::vector<uint8_t> buf;
  std::vector<ColTap> taps;
  const size_t item = (size_t)job->out_h * job->out_w * 3;
  int w, h;
  for (;;) {
    int i = job->next.fetch_add(1);
    if (i >= job->n) return;
    typename Store::type* dst = out + (size_t)i * item;
    bool ok = false;
    try {
      ok = decode_image(job->paths[i], buf, w, h);
    } catch (...) {
      // an exception escaping a pool thread would std::terminate the
      // whole trainer; a failed slot must degrade like any other
      ok = false;
    }
    if (!ok) {
      // unreadable/unsupported/oversized: zero-fill; caller retries via PIL
      std::memset(dst, 0, sizeof(typename Store::type) * item);
      job->errors.fetch_add(1);
      continue;
    }
    Rng rng(job->seed * 0x9E3779B97f4A7C15ULL + (uint64_t)i * 0xD1B54A32D192ED03ULL);
    double x0, y0, cw, ch;
    bool flip = false;
    if (job->train) {
      rrc_box(rng, w, h, job->scale_min, job->scale_max, x0, y0, cw, ch);
      flip = rng.uniform() < 0.5;
    } else {
      // Resize(resize_short) + CenterCrop(out): equivalent single resample —
      // crop box side = out/resize_short · short_side, centered
      double scale = (double)std::min(w, h) / job->resize_short;
      cw = job->out_w * scale;
      ch = job->out_h * scale;
      x0 = (w - cw) / 2.0;
      y0 = (h - ch) / 2.0;
    }
    crop_resize_store(buf.data(), w, h, x0, y0, cw, ch, dst, job->out_w,
                      job->out_h, flip, taps, store);
  }
}

// Fan one batch over `num_threads` workers; returns the decode failures.
template <class Store>
int load_batch(const char** paths, int n, typename Store::type* out,
               int out_h, int out_w, int train, int resize_short,
               double scale_min, double scale_max, uint64_t seed,
               int num_threads, Store store) {
  BatchJob job;
  job.paths = paths;
  job.n = n;
  job.out_h = out_h;
  job.out_w = out_w;
  job.train = train;
  job.resize_short = resize_short;
  job.scale_min = scale_min;
  job.scale_max = scale_max;
  job.seed = seed;
  int t = std::max(1, std::min(num_threads, n));
  if (t == 1) {
    worker<Store>(&job, out, store);
  } else {
    std::vector<std::thread> threads;
    threads.reserve(t);
    for (int i = 0; i < t; ++i)
      threads.emplace_back(worker<Store>, &job, out, store);
    for (auto& th : threads) th.join();
  }
  return job.errors.load();
}

}  // namespace

extern "C" {

// Fill out[n, out_h, out_w, 3] float32, normalized with mean/std. Returns
// number of decode failures (their slots are zero-filled; indices of
// failures are not reported — the Python wrapper re-loads failed slots
// through PIL when the count is >0).
int dp_load_batch(const char** paths, int n, float* out, int out_h, int out_w,
                  int train, int resize_short, double scale_min,
                  double scale_max, uint64_t seed, const float* mean,
                  const float* stdv, int num_threads) {
  return load_batch(paths, n, out, out_h, out_w, train, resize_short,
                    scale_min, scale_max, seed, num_threads,
                    StoreF32{mean, stdv});
}

// Fill out[n, out_h, out_w, 3] uint8 with the quantized 0..255 pixels (the
// uint8 wire); same crops, flips and failure reporting as dp_load_batch.
int dp_load_batch_u8(const char** paths, int n, uint8_t* out, int out_h,
                     int out_w, int train, int resize_short, double scale_min,
                     double scale_max, uint64_t seed, int num_threads) {
  return load_batch(paths, n, out, out_h, out_w, train, resize_short,
                    scale_min, scale_max, seed, num_threads, StoreU8{});
}

// Capability probe: 1 when this build decodes PNG, 0 for the JPEG-only
// -DDP_NO_PNG fallback (callers/tests can degrade instead of failing).
int dp_has_png(void) {
#ifndef DP_NO_PNG
  return 1;
#else
  return 0;
#endif
}

// Probe a JPEG/PNG: returns 0 on success and writes w/h; -1 on failure.
int dp_probe_image(const char* path, int* w, int* h) {
  std::vector<uint8_t> buf;
  int ww, hh;
  if (!decode_image(path, buf, ww, hh)) return -1;
  *w = ww;
  *h = hh;
  return 0;
}

}  // extern "C"
