/* BlueStore-lite's transaction I/O, many transactions in one call.
 *
 * The stores (ceph_tpu/backend/bluestore.py) stage a transaction under
 * the interpreter and hand its writes here through ctypes, which lets
 * the interpreter go once for the whole call: for each transaction, its
 * blobs' bytes at their allocated offsets, the block fsync where sync,
 * then the journal's frame and record appended and its fsync where sync
 * — data precedes its metadata, as in BlueStore's txc (aio, then kv
 * commit).  A group commit passes every store's queued transactions at
 * once, each store's in their order: the twelve sub-writes of a put
 * cost one release of the interpreter where, made call by call from
 * Python, each of some eighty calls let it go and had to win it back.
 * Each transaction's two stretches (monotonic ns) and the thread's CPU
 * time over both come back beside its result, so that block_io_us,
 * wal_io_us and commit_cpu_us time the calls and not the wait for the
 * interpreter after them.
 */
#include <cerrno>
#include <cstdint>
#include <ctime>
#include <vector>

#include <sys/uio.h>
#include <unistd.h>

namespace {

int64_t now_ns(clockid_t clock = CLOCK_MONOTONIC) {
    timespec ts;
    clock_gettime(clock, &ts);
    return int64_t(ts.tv_sec) * 1000000000 + ts.tv_nsec;
}

int pwrite_all(int fd, const char *p, int64_t n, int64_t off) {
    while (n > 0) {
        ssize_t w = pwrite(fd, p, size_t(n), off);
        if (w < 0) {
            if (errno == EINTR) continue;
            return -errno;
        }
        p += w;
        n -= w;
        off += w;
    }
    return 0;
}

int write_all(int fd, const char *p, int64_t n) {
    while (n > 0) {
        ssize_t w = write(fd, p, size_t(n));
        if (w < 0) {
            if (errno == EINTR) continue;
            return -errno;
        }
        p += w;
        n -= w;
    }
    return 0;
}

/* The frame and the record appended (one writev, the rest of a short
 * one in order). */
int append2(int fd, const char *a, int64_t na, const char *b, int64_t nb) {
    iovec iov[2] = {{const_cast<char *>(a), size_t(na)},
                    {const_cast<char *>(b), size_t(nb)}};
    ssize_t w;
    do {
        w = writev(fd, iov, 2);
    } while (w < 0 && errno == EINTR);
    if (w < 0) return -errno;
    if (w < na) {
        int rc = write_all(fd, a + w, na - w);
        if (rc) return rc;
        w = na;
    }
    return write_all(fd, b + (w - na), nb - (w - na));
}

}  // namespace

/* ntx transactions; transaction i has nblobs[i] blobs, taken in order
 * from bufs/lens/offs.  rcs[i]: 0, or -errno of its first call that
 * failed (nothing of it after that is written), or -ECANCELED where an
 * earlier transaction on the same journal failed.  ns[3i..3i+2]: its
 * block stretch, its journal stretch, the thread's CPU over both.
 * Returns the number of transactions not written. */
extern "C" int ec_store_commit_batch(
        int ntx, const int *block_fds, const int *wal_fds, const int *syncs,
        const int *nblobs, const char *const *bufs, const int64_t *lens,
        const int64_t *offs, const char *const *frames,
        const int64_t *frame_lens, const char *const *recs,
        const int64_t *rec_lens, int *rcs, int64_t *ns) {
    std::vector<int> failed;            // journals that failed this call
    int bad = 0, b = 0;
    for (int i = 0; i < ntx; b += nblobs[i], ++i) {
        int rc = 0;
        for (int fd : failed)
            if (fd == wal_fds[i]) rc = -ECANCELED;
        int64_t c0 = now_ns(CLOCK_THREAD_CPUTIME_ID), t0 = now_ns(), t1 = t0;
        for (int j = 0; !rc && j < nblobs[i]; ++j)
            rc = pwrite_all(block_fds[i], bufs[b + j], lens[b + j],
                            offs[b + j]);
        if (!rc && syncs[i] && fsync(block_fds[i])) rc = -errno;
        if (!rc) {
            t1 = now_ns();
            rc = append2(wal_fds[i], frames[i], frame_lens[i], recs[i],
                         rec_lens[i]);
        }
        if (!rc && syncs[i] && fsync(wal_fds[i])) rc = -errno;
        int64_t t2 = now_ns();
        rcs[i] = rc;
        ns[3 * i] = t1 - t0;
        ns[3 * i + 1] = t2 - t1;
        ns[3 * i + 2] = now_ns(CLOCK_THREAD_CPUTIME_ID) - c0;
        if (rc) {
            ++bad;
            if (rc != -ECANCELED) failed.push_back(wal_fds[i]);
        }
    }
    return bad;
}
