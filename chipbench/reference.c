/* The two update rules of chipbench/reference.py in C, applied in place to
 * the per-node degree d, community c and community volume v, over rows
 * [0, rows) of an (rows, 2) int32 edge array.  A row with a negative id or
 * i == j is skipped.  Built and called by chipbench/reference.py. */
#include <stdint.h>

/* The paper's Algorithm 1, one edge at a time. */
void sequential(const int32_t *e, int64_t rows, int32_t *d, int32_t *c,
                int32_t *v, int32_t v_max) {
  for (int64_t t = 0; t < rows; t++) {
    int32_t i = e[2 * t], j = e[2 * t + 1];
    if (i == j || i < 0 || j < 0) continue;
    d[i] += 1;
    d[j] += 1;
    int32_t ci = c[i], cj = c[j];
    v[ci] += 1;
    v[cj] += 1;
    if (v[ci] <= v_max && v[cj] <= v_max) {
      if (v[ci] <= v[cj]) { /* i joins the community of j */
        v[cj] += d[i];
        v[ci] -= d[i];
        c[i] = cj;
      } else { /* j joins the community of i */
        v[ci] += d[j];
        v[cj] -= d[j];
        c[j] = ci;
      }
    }
  }
}

/* The Jacobi form over consecutive chunks of `chunk` rows from row 0 (the
 * last one may be short): the chunk's degrees and volumes are added first,
 * every decision reads those volumes and the communities from before the
 * chunk, and where several edges would move one node, the first in stream
 * order moves it.  `moved` is n zeros, and is zeros again on return;
 * `buf` holds 3 * chunk words. */
void jacobi(const int32_t *e, int64_t rows, int32_t chunk, int32_t *d,
            int32_t *c, int32_t *v, int32_t v_max, int32_t *moved,
            int32_t *buf) {
  for (int64_t lo = 0; lo < rows; lo += chunk) {
    int64_t hi = lo + chunk < rows ? lo + chunk : rows;
    for (int64_t t = lo; t < hi; t++) {
      int32_t i = e[2 * t], j = e[2 * t + 1];
      if (i == j || i < 0 || j < 0) continue;
      d[i] += 1;
      d[j] += 1;
      v[c[i]] += 1;
      v[c[j]] += 1;
    }
    int64_t k = 0; /* decisions: (mover, target, source) */
    for (int64_t t = lo; t < hi; t++) {
      int32_t i = e[2 * t], j = e[2 * t + 1];
      if (i == j || i < 0 || j < 0) continue;
      int32_t ci = c[i], cj = c[j];
      if (v[ci] > v_max || v[cj] > v_max) continue;
      int i_joins = v[ci] <= v[cj];
      buf[3 * k] = i_joins ? i : j;
      buf[3 * k + 1] = i_joins ? cj : ci;
      buf[3 * k + 2] = i_joins ? ci : cj;
      k++;
    }
    for (int64_t t = 0; t < k; t++) {
      int32_t mover = buf[3 * t];
      if (moved[mover]) continue;
      moved[mover] = 1;
      v[buf[3 * t + 1]] += d[mover];
      v[buf[3 * t + 2]] -= d[mover];
      c[mover] = buf[3 * t + 1];
    }
    for (int64_t t = 0; t < k; t++) moved[buf[3 * t]] = 0;
  }
}
