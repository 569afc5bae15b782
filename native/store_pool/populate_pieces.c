/* The store pool's walk over a spare's pieces (ceph_tpu/cluster/store.py,
 * `_make_spare`): the refill thread calls this ONCE a spare through ctypes,
 * which lets the GIL go for the whole walk, where a Python `for` came back
 * to it after every piece.  What the kernel is asked is what the Python
 * walk asks: `piece` bytes at a time, each piece one
 * mmap(MAP_FIXED | MAP_POPULATE) over the mapping's own range, in order, so
 * nobody who maps or faults waits longer for the address space than one
 * piece's populate lasts.  The range stays the caller's mapping: nothing
 * here maps outside [base, base + n) or unmaps anything.
 *
 * No binary of this is committed: the refill thread builds it with the
 * host's `cc` (store.py, `_native_walk`) and walks in Python where it
 * cannot.
 */
#define _GNU_SOURCE
#include <errno.h>
#include <stddef.h>
#include <sys/mman.h>

/* 0, or the errno of the piece the kernel refused (the pieces before it
 * are populated, the rest of the range is as it was) */
int populate_pieces(void *base, size_t n, size_t piece)
{
    char *at = base;
    size_t off, len;

    if (piece == 0)
        return EINVAL;
    for (off = 0; off < n; off += len) {
        len = n - off < piece ? n - off : piece;
        if (mmap(at + off, len, PROT_READ | PROT_WRITE,
                 MAP_PRIVATE | MAP_ANONYMOUS | MAP_FIXED | MAP_POPULATE,
                 -1, 0) != (void *)(at + off))
            return errno ? errno : EINVAL;
    }
    return 0;
}
