"""Core sketching correctness: structural identities (exact), statistical
properties (unbiasedness, variance ordering FCS <= TS, Cor.1 scaling), and
hypothesis property tests (linearity/scaling invariants)."""
import jax
import jax.numpy as jnp
import numpy as np
from hypothesis import given, settings
from hypothesis import strategies as st

from repro.core import (
    fcs_cp, fcs_general, fcs_kron_compress, fcs_kron_decompress,
    fcs_sketch_len, fcs_tiuu, hcs_cp, hcs_general,
    make_mode_hash, make_tensor_hashes, ts_cp, ts_general,
)
from repro.core.hashes import combined_fcs_hash

KEY = jax.random.PRNGKey(0)


def _cp_tensor(key, dims, R):
    ks = jax.random.split(key, len(dims) + 1)
    lam = jax.random.uniform(ks[0], (R,)) + 0.5
    Us = [jax.random.normal(k, (d, R)) for k, d in zip(ks[1:], dims)]
    T = jnp.einsum("ar,br,cr,r->abc", *Us, lam)
    return lam, Us, T


class TestStructuralIdentities:
    dims = (17, 13, 11)

    def setup_method(self, _):
        self.hashes = make_tensor_hashes(KEY, self.dims, 16, 3)
        self.lam, self.Us, self.T = _cp_tensor(jax.random.PRNGKey(1),
                                               self.dims, 4)

    def test_fcs_equals_structured_long_cs(self):
        """Eq. 6: FCS(T) == CS(vec(T)) under the structured hash pair."""
        sk = fcs_general(self.T, self.hashes)
        hc, sc = combined_fcs_hash(self.hashes)
        Jt = fcs_sketch_len([mh.J for mh in self.hashes])
        ref = jnp.stack([
            jnp.zeros(Jt).at[hc[d]].add(sc[d] * self.T.reshape(-1))
            for d in range(3)])
        np.testing.assert_allclose(sk, ref, rtol=1e-4, atol=1e-4)

    def test_fcs_cp_equals_general(self):
        """Eq. 8: the FFT fast path equals the definition."""
        np.testing.assert_allclose(fcs_cp(self.lam, self.Us, self.hashes),
                                   fcs_general(self.T, self.hashes),
                                   rtol=3e-3, atol=3e-3)

    def test_ts_cp_equals_general(self):
        np.testing.assert_allclose(ts_cp(self.lam, self.Us, self.hashes),
                                   ts_general(self.T, self.hashes),
                                   rtol=3e-3, atol=3e-3)

    def test_hcs_cp_equals_general(self):
        np.testing.assert_allclose(hcs_cp(self.lam, self.Us, self.hashes),
                                   hcs_general(self.T, self.hashes),
                                   rtol=3e-3, atol=3e-3)

    def test_fcs_sketch_len(self):
        assert fcs_sketch_len([16, 16, 16]) == 46
        assert fcs_sketch_len([4, 8]) == 11

    def test_tiuu_z_trick_equals_direct(self):
        """Eq. 17 == explicit <FCS(T), FCS(e_i o u o u)>."""
        hashes = make_tensor_hashes(jax.random.PRNGKey(3), (11, 11, 11),
                                    64, 3)
        _, Us, T = _cp_tensor(jax.random.PRNGKey(4), (11, 11, 11), 2)
        u = jax.random.normal(jax.random.PRNGKey(5), (11,))
        u = u / jnp.linalg.norm(u)
        sk = fcs_general(T, hashes)
        est = fcs_tiuu(sk, u, hashes)
        direct = []
        for i in range(11):
            e = jnp.zeros(11).at[i].set(1.0)
            ski = fcs_cp(jnp.ones(1), [e[:, None], u[:, None], u[:, None]],
                         hashes)
            direct.append(jnp.sum(sk * ski, axis=-1))
        np.testing.assert_allclose(est, jnp.stack(direct, axis=1),
                                   rtol=2e-3, atol=2e-3)


class TestStatistics:
    def test_inner_product_unbiased(self):
        """<FCS(M), FCS(N)> is a consistent estimator of <M, N> (Prop. 1)."""
        dims = (8, 8, 8)
        kM, kN = jax.random.split(jax.random.PRNGKey(2))
        M = jax.random.normal(kM, dims)
        N = jax.random.normal(kN, dims)
        exact = float(jnp.vdot(M, N))
        hashes = make_tensor_hashes(jax.random.PRNGKey(7), dims, 64, 256)
        est = jnp.sum(fcs_general(M, hashes) * fcs_general(N, hashes),
                      axis=-1)
        mean = float(jnp.mean(est))
        sem = float(jnp.std(est) / np.sqrt(256))
        assert abs(mean - exact) < 5 * sem + 1e-3

    def test_fcs_variance_not_worse_than_ts(self):
        """Prop. 1 (Eq. 14): Var[FCS estimator] <= Var[TS estimator] under
        equalized hashes.  Checked empirically over repetitions."""
        dims = (8, 8, 8)
        kM, kN = jax.random.split(jax.random.PRNGKey(2))
        M = jax.random.normal(kM, dims)
        N = jax.random.normal(kN, dims)
        hashes = make_tensor_hashes(jax.random.PRNGKey(11), dims, 32, 512)
        e_fcs = jnp.sum(fcs_general(M, hashes) * fcs_general(N, hashes), -1)
        e_ts = jnp.sum(ts_general(M, hashes) * ts_general(N, hashes), -1)
        v_fcs = float(jnp.var(e_fcs))
        v_ts = float(jnp.var(e_ts))
        assert v_fcs <= v_ts * 1.10  # 10% slack for sampling noise

    def test_variance_scales_inversely_with_J(self):
        """Cor. 1: estimator variance ~ ||T||^2 / J."""
        dims = (8, 8, 8)
        M = jax.random.normal(jax.random.PRNGKey(2), dims)
        N = jax.random.normal(jax.random.PRNGKey(3), dims)
        vs = []
        for J in (16, 64):
            hashes = make_tensor_hashes(jax.random.PRNGKey(13), dims, J, 384)
            e = jnp.sum(fcs_general(M, hashes) * fcs_general(N, hashes), -1)
            vs.append(float(jnp.var(e)))
        # J x4 => variance should drop noticeably (allow wide slack)
        assert vs[1] < vs[0] * 0.6

    def test_norm_preservation(self):
        dims = (10, 10, 10)
        T = jax.random.normal(jax.random.PRNGKey(5), dims)
        hashes = make_tensor_hashes(jax.random.PRNGKey(6), dims, 256, 64)
        sk = fcs_general(T, hashes)
        norms = jnp.sum(sk ** 2, axis=-1)
        rel = float(jnp.abs(jnp.mean(norms) - jnp.sum(T ** 2))
                    / jnp.sum(T ** 2))
        assert rel < 0.15


class TestHypothesisProperties:
    @settings(max_examples=20, deadline=None)
    @given(scale=st.floats(-3.0, 3.0),
           seed=st.integers(0, 2 ** 16))
    def test_linearity_scaling(self, scale, seed):
        """FCS(a*T) == a*FCS(T) (sketches are linear maps)."""
        dims = (5, 6, 7)
        T = jax.random.normal(jax.random.PRNGKey(seed % 97), dims)
        hashes = make_tensor_hashes(jax.random.PRNGKey(seed), dims, 8, 2)
        a = jnp.float32(scale)
        np.testing.assert_allclose(fcs_general(a * T, hashes),
                                   a * fcs_general(T, hashes),
                                   rtol=1e-3, atol=1e-4)

    @settings(max_examples=20, deadline=None)
    @given(seed=st.integers(0, 2 ** 16))
    def test_additivity(self, seed):
        dims = (5, 6, 7)
        kA, kB = jax.random.split(jax.random.PRNGKey(seed % 89))
        A = jax.random.normal(kA, dims)
        B = jax.random.normal(kB, dims)
        hashes = make_tensor_hashes(jax.random.PRNGKey(seed), dims, 8, 2)
        np.testing.assert_allclose(
            fcs_general(A + B, hashes),
            fcs_general(A, hashes) + fcs_general(B, hashes),
            rtol=1e-3, atol=1e-4)

    @settings(max_examples=10, deadline=None)
    @given(seed=st.integers(0, 2 ** 16),
           J=st.sampled_from([4, 8, 16]))
    def test_hash_range(self, seed, J):
        mh = make_mode_hash(jax.random.PRNGKey(seed), 50, J, 3)
        assert int(mh.h.min()) >= 0 and int(mh.h.max()) < J
        assert set(np.unique(np.asarray(mh.s))).issubset({-1.0, 1.0})


def test_kron_compress_decompress_improves_with_J():
    A = jax.random.normal(jax.random.PRNGKey(1), (6, 5))
    B = jax.random.normal(jax.random.PRNGKey(2), (4, 7))
    K = jnp.kron(A, B)
    errs = []
    for J in (64, 512):
        hk = make_tensor_hashes(jax.random.PRNGKey(3), (6, 5, 4, 7), J, 9)
        Khat = fcs_kron_decompress(fcs_kron_compress(A, B, hk), hk,
                                   (6, 5), (4, 7))
        errs.append(float(jnp.linalg.norm(Khat - K) / jnp.linalg.norm(K)))
    assert errs[1] < errs[0]
