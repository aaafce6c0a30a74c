"""Numerical checks of the paper's theory (Lemma 1, Remark 1, Thm 1/2
convergence behavior, Corollary 1 dynamic-rank safety)."""
import numpy as np
import jax
import jax.numpy as jnp

from repro.core import graft, projection
from repro.core.features import svd_features
from repro.core.maxvol import fast_maxvol


class TestTheorem1Convergence:
    def test_projected_gd_converges_when_error_bounded(self, rng):
        """GD with gradient projected onto a subspace containing most of ḡ
        converges to a small-gradient region (Thm 1: ‖∇L‖ ≤ εG)."""
        d = 30
        A = rng.normal(size=(d, d)).astype(np.float32)
        Q_ = A @ A.T / d + np.eye(d, dtype=np.float32)   # strongly convex
        w_star = rng.normal(size=(d,)).astype(np.float32)

        def grad(w):
            return Q_ @ (w - w_star)

        w = np.zeros(d, np.float32)
        for _ in range(400):
            g = grad(w)
            # projection basis: top-8 directions of recent gradients + noise
            basis = np.stack([grad(w + 0.01 * rng.normal(size=d))
                              for _ in range(8)], 1)
            q, _ = np.linalg.qr(basis)
            g_proj = q @ (q.T @ g)
            w = w - 0.05 * g_proj
        assert np.linalg.norm(grad(w)) < 0.1 * np.linalg.norm(grad(np.zeros(d)))

    def test_unbounded_error_stalls(self, rng):
        """Projecting onto a near-orthogonal subspace must NOT converge —
        the ε in the bound is real, not slack."""
        d = 30
        w_star = np.ones(d, np.float32)

        def grad(w):
            return w - w_star

        w = np.zeros(d, np.float32)
        # fixed basis orthogonal to the gradient direction 1/√d
        ones = np.ones((d, 1)) / np.sqrt(d)
        B = np.linalg.qr(rng.normal(size=(d, 5)) -
                         ones @ (ones.T @ rng.normal(size=(d, 5))))[0]
        for _ in range(200):
            g = grad(w)
            w = w - 0.1 * B @ (B.T @ g)
        # gradient norm stays large: projection killed the descent direction
        assert np.linalg.norm(grad(w)) > 0.5 * np.linalg.norm(grad(np.zeros(d)))


class TestCorollary1:
    def test_rank_grows_until_error_below_eps(self, rng):
        """Dynamic rank adjustment: for gradients with r-dim structure the
        selected rank tracks r as eps tightens."""
        d, K = 40, 64
        for true_rank in (2, 6):
            basis = rng.normal(size=(d, true_rank)).astype(np.float32)
            G = (basis @ rng.normal(size=(true_rank, K))).astype(np.float32)
            G += 1e-4 * rng.normal(size=(d, K)).astype(np.float32)
            gb = jnp.asarray(G.mean(1))
            V = svd_features(jnp.asarray(G).T, 16)
            cfg = graft.GraftConfig(rset=(1, 2, 4, 6, 8, 16), eps=1e-3)
            st = graft.graft_select(cfg, V, jnp.asarray(G), gb, jnp.int32(0))
            assert int(st.rank) <= max(true_rank, 1) + 2
            assert float(st.last_error) <= 1e-3 + 1e-4


class TestAlignmentFigure2:
    def test_alignment_improves_with_rank(self, rng):
        """cos(subset ḡ, batch ḡ) grows with subset size (Fig 2b trend)."""
        d, K = 32, 64
        G = rng.normal(size=(d, K)).astype(np.float32)
        G[:, : K // 2] += 3 * rng.normal(size=(d, 1)).astype(np.float32)
        gb = jnp.asarray(G.mean(1))
        V = svd_features(jnp.asarray(G).T, 16)
        piv, _ = fast_maxvol(V, 16)
        aligns = []
        for r in (2, 8, 16):
            sub = jnp.asarray(G)[:, np.asarray(piv)[:r]].mean(1)
            aligns.append(float(projection.cosine_alignment(sub, gb)))
        assert aligns[-1] >= aligns[0] - 0.05


class TestComplexityScaling:
    def test_fast_maxvol_quadratic_in_R(self, rng):
        """Operation-count proxy: FLOP estimate of the jitted fast_maxvol
        scales ~O(K·R²) (paper Table 7)."""
        import jax
        K = 512

        def flops(R):
            V = jnp.zeros((K, R), jnp.float32)
            c = jax.jit(lambda v: fast_maxvol(v, R)).lower(V).compile()
            return c.cost_analysis().get("flops", 0.0)

        f8, f16, f32 = flops(8), flops(16), flops(32)
        # growth ratio between successive doublings should be ≲ 4 (R² term)
        # and ≳ 1.6 (definitely superlinear)
        assert 1.6 < f32 / f16 < 5.0, (f8, f16, f32)
