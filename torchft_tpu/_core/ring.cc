// The inbound half of an exact ring step (backends/host.py), outside the
// interpreter: one ctypes call (GIL released for all of it) receives a
// whole chunk from the previous neighbour's socket and, for the
// reduce-scatter phase, folds it into the accumulator piece by piece as
// it lands.
//
// The fold is `out[i] = mine[i] + recv[i]`: the operands and order of the
// Python loop's `np.add(mine, recv, out=out)`, one IEEE add an element, so
// the sums are bitwise those. Nothing here reassociates (there is nothing
// to reassociate) and the core is not built with -ffast-math; the loop has
// to vectorise at the Release flags (-O3), a scalar fold is slower than
// numpy's.
//
// Python sockets with a timeout are non-blocking underneath, and every
// call here asks for that itself (MSG_DONTWAIT): recv() and, on EAGAIN,
// poll() with the ring's timeout (< 0 = wait for ever). Closing
// a ring shuts its sockets down first (_Ring.close), which is what wakes a
// blocked poll/recv here on abort or reconfigure.
//
// Convention as capi.cc: 0 on success, -1 on error with *err set to a
// malloc'd message the caller frees with tft_free.

#include <errno.h>
#include <poll.h>
#include <stdint.h>
#include <stdlib.h>
#include <string.h>
#include <sys/socket.h>
#include <sys/types.h>

namespace {

// One piece of the inbound chunk: 256 KB stays in L2 between the kernel's
// copy out of the socket and the fold that reads it back (host.py's
// _SEG_BYTES; a power of two, so a piece boundary is element-aligned).
constexpr size_t kPieceBytes = 1 << 18;

int fail(char** err, const char* msg) {
  if (err) *err = strdup(msg);
  return -1;
}

// Receive exactly n bytes into dst. With `got`, the peer's close is not an
// error: *got says how many bytes arrived before it (n otherwise).
int recv_exact(int fd, char* dst, size_t n, int64_t timeout_ms, char** err,
               size_t* got_out = nullptr) {
  size_t got = 0;
  while (got < n) {
    ssize_t r = recv(fd, dst + got, n - got, MSG_DONTWAIT);
    if (r > 0) {
      got += (size_t)r;
      continue;
    }
    if (r == 0) {
      if (got_out) break;
      return fail(err, "peer closed connection");
    }
    if (errno == EINTR) continue;
    if (errno != EAGAIN && errno != EWOULDBLOCK)
      return fail(err, strerror(errno));
    struct pollfd p = {fd, POLLIN, 0};
    int pr = poll(&p, 1, timeout_ms < 0 ? -1 : (int)timeout_ms);
    if (pr == 0) return fail(err, "timed out");
    if (pr < 0 && errno != EINTR) return fail(err, strerror(errno));
    // Readable, hung up or in error: the next recv() says which.
  }
  if (got_out) *got_out = got;
  return 0;
}

// Send exactly n bytes from src: send() and, on EAGAIN, poll() as above.
int send_all(int fd, const char* src, size_t n, int64_t timeout_ms,
             char** err) {
  size_t put = 0;
  while (put < n) {
    ssize_t r = send(fd, src + put, n - put, MSG_NOSIGNAL | MSG_DONTWAIT);
    if (r >= 0) {
      put += (size_t)r;
      continue;
    }
    if (errno == EINTR) continue;
    if (errno != EAGAIN && errno != EWOULDBLOCK)
      return fail(err, strerror(errno));
    struct pollfd p = {fd, POLLOUT, 0};
    int pr = poll(&p, 1, timeout_ms < 0 ? -1 : (int)timeout_ms);
    if (pr == 0) return fail(err, "timed out");
    if (pr < 0 && errno != EINTR) return fail(err, strerror(errno));
  }
  return 0;
}

// `mine` and `out` may be the same pointer (the in-place spelling), so
// only the scratch is restrict.
template <typename T>
void fold(const T* mine, const T* __restrict recv, T* out, size_t n) {
  for (size_t i = 0; i < n; i++) out[i] = mine[i] + recv[i];
}

template <typename T>
int recv_fold(int fd, const char* mine, char* out, size_t nbytes,
              int64_t timeout_ms, char** err) {
  if (nbytes % sizeof(T)) return fail(err, "ring fold: ragged byte count");
  // Aligned for any vector width the compiler picks; freed on every path.
  void* scratch = aligned_alloc(64, kPieceBytes);
  if (!scratch) return fail(err, "ring fold: out of memory");
  int rc = 0;
  for (size_t off = 0; off < nbytes;) {
    size_t k = nbytes - off < kPieceBytes ? nbytes - off : kPieceBytes;
    rc = recv_exact(fd, (char*)scratch, k, timeout_ms, err);
    if (rc != 0) break;
    fold<T>((const T*)(mine + off), (const T*)scratch, (T*)(out + off),
            k / sizeof(T));
    off += k;
  }
  free(scratch);
  return rc;
}

}  // namespace

extern "C" {

// Element types the core folds (_native.py RING_FOLD_DTYPES maps numpy dtypes
// to these codes).
enum { kF32 = 0, kF64 = 1, kI32 = 2, kI64 = 3 };

int tft_ring_recv_fold(int fd, const void* mine, void* out, size_t nbytes,
                       int32_t dtype, int64_t timeout_ms, char** err) {
  const char* m = (const char*)mine;
  char* o = (char*)out;
  switch (dtype) {
    case kF32:
      return recv_fold<float>(fd, m, o, nbytes, timeout_ms, err);
    case kF64:
      return recv_fold<double>(fd, m, o, nbytes, timeout_ms, err);
    // Unsigned lanes: numpy's integer add wraps, signed overflow in C is
    // undefined; the bits are the same.
    case kI32:
      return recv_fold<uint32_t>(fd, m, o, nbytes, timeout_ms, err);
    case kI64:
      return recv_fold<uint64_t>(fd, m, o, nbytes, timeout_ms, err);
  }
  return fail(err, "ring fold: unsupported dtype");
}

int tft_ring_recv_exact(int fd, void* out, size_t nbytes, int64_t timeout_ms,
                        char** err) {
  return recv_exact(fd, (char*)out, nbytes, timeout_ms, err);
}

// A body chunk of the HTTP tiers (transport.py) in one foreign call a
// side: read until nbytes have arrived or the peer closed (*got), and
// written whole.
int tft_sock_recv_into(int fd, void* out, size_t nbytes, int64_t timeout_ms,
                       size_t* got, char** err) {
  return recv_exact(fd, (char*)out, nbytes, timeout_ms, err, got);
}

int tft_sock_send_all(int fd, const void* src, size_t nbytes,
                      int64_t timeout_ms, char** err) {
  return send_all(fd, (const char*)src, nbytes, timeout_ms, err);
}

}  // extern "C"
