/* Plain loops of the benchmark's reference GBDT (harness/reference.py).
 * Built once per checkout by harness/native.py; nothing of lightgbm_tpu
 * is read here.  Every function works on a contiguous run of rows so
 * that Python can hand disjoint runs to a few threads. */
#include <math.h>
#include <stdint.h>
#include <stddef.h>

/* value -> bin: the first bin whose upper bound is >= x.
 * uppers is F rows of 256 doubles, padded with +inf. */
void ref_bin_rows(const float *x, int64_t n, int32_t f_count,
                  const double *uppers, const int32_t *nbins,
                  uint8_t *out)
{
    for (int64_t i = 0; i < n; ++i) {
        const float *row = x + i * f_count;
        uint8_t *o = out + i * f_count;
        for (int32_t f = 0; f < f_count; ++f) {
            const double *u = uppers + (size_t)f * 256;
            double v = (double)row[f];
            int32_t lo = 0, hi = nbins[f] - 1;
            while (lo < hi) {
                int32_t mid = (lo + hi) >> 1;
                if (v <= u[mid]) hi = mid; else lo = mid + 1;
            }
            o[f] = (uint8_t)lo;
        }
    }
}

/* histogram of the rows idx[0..n): out[f][bin] += (g, h, 1). */
void ref_hist_rows(const uint8_t *bins, int32_t f_count,
                   const int32_t *idx, int64_t n,
                   const double *g, const double *h, double *out)
{
    for (int64_t k = 0; k < n; ++k) {
        int64_t i = idx[k];
        const uint8_t *row = bins + i * f_count;
        double gi = g[i], hi = h[i];
        for (int32_t f = 0; f < f_count; ++f) {
            double *o = out + ((size_t)f * 256 + row[f]) * 3;
            o[0] += gi; o[1] += hi; o[2] += 1.0;
        }
    }
}

/* stable partition of idx by bin <= thr on one feature; returns the
 * number that go left.  left and right each have room for n. */
int64_t ref_split_rows(const uint8_t *bins, int32_t f_count,
                       const int32_t *idx, int64_t n, int32_t feature,
                       int32_t thr, int32_t *left, int32_t *right)
{
    int64_t nl = 0, nr = 0;
    for (int64_t k = 0; k < n; ++k) {
        int32_t i = idx[k];
        if (bins[(int64_t)i * f_count + feature] <= thr) left[nl++] = i;
        else right[nr++] = i;
    }
    return nl;
}

/* route rows through a tree on the raw values: node >= 0 is internal,
 * a child < 0 is the leaf ~child.  x <= threshold goes left. */
void ref_route_rows(const float *x, int64_t n, int32_t f_count,
                    const int32_t *feature, const double *threshold,
                    const int32_t *left, const int32_t *right,
                    int32_t n_internal, int32_t *leaf_out)
{
    for (int64_t i = 0; i < n; ++i) {
        const float *row = x + i * f_count;
        int32_t node = n_internal > 0 ? 0 : -1;
        while (node >= 0)
            node = ((double)row[feature[node]] <= threshold[node])
                       ? left[node] : right[node];
        leaf_out[i] = ~node;
    }
}

/* LambdaRank (NDCG) of the queries [q0, q1): query q is the rows
 * bounds[q] .. bounds[q+1]; disc[i] is row i's discount at its
 * position in its query sorted by score; inv_max[q] one over the
 * query's best DCG; gains[l] label l's gain.  Every pair of a query
 * whose labels differ adds its lambda and hessian to both rows; g and
 * h come in zeroed. */
void ref_lambdarank(const double *score, const int32_t *label,
                    const double *disc, const int64_t *bounds,
                    const double *inv_max, int64_t q0, int64_t q1,
                    const double *gains, double sigmoid,
                    double norm_floor, double *g, double *h)
{
    for (int64_t q = q0; q < q1; ++q) {
        int64_t lo = bounds[q], hi = bounds[q + 1];
        double im = inv_max[q];
        if (im <= 0.0 || hi - lo < 2) continue;
        double best = score[lo], worst = score[lo];
        for (int64_t i = lo + 1; i < hi; ++i) {
            if (score[i] > best) best = score[i];
            if (score[i] < worst) worst = score[i];
        }
        int norm = best != worst;
        for (int64_t i = lo; i < hi; ++i) {
            for (int64_t j = i + 1; j < hi; ++j) {
                int64_t a, b;           /* a: the higher label */
                if (label[i] > label[j]) { a = i; b = j; }
                else if (label[i] < label[j]) { a = j; b = i; }
                else continue;
                double ds = score[a] - score[b];
                double delta = (gains[label[a]] - gains[label[b]])
                               * fabs(disc[a] - disc[b]) * im;
                if (norm) delta /= norm_floor + fabs(ds);
                double p = 2.0 / (1.0 + exp(2.0 * sigmoid * ds));
                double lam = delta * p;
                double hes = 2.0 * delta * p * (2.0 - p);
                g[a] -= lam; g[b] += lam;
                h[a] += hes; h[b] += hes;
            }
        }
    }
}
