"""Content fingerprints: equality, sensitivity, memoization, tokens."""
import jax
import jax.numpy as jnp
import pytest
from jax.experimental import sparse as jsparse

from repro.core import linop
from repro.serve import Fingerprint, digest_array, fingerprint


def _A(seed=0, shape=(50, 7)):
    return jax.random.normal(jax.random.PRNGKey(seed), shape)


def test_same_content_same_fingerprint():
    A = _A()
    B = jnp.array(A)  # distinct object, identical bytes
    assert fingerprint(A) == fingerprint(B)
    assert hash(fingerprint(A)) == hash(fingerprint(B))


def test_content_sensitivity():
    A = _A()
    B = A.at[3, 4].add(1e-12)
    assert fingerprint(A) != fingerprint(B)


def test_config_sensitivity():
    A = _A()
    base = fingerprint(A)
    assert fingerprint(A, reg=0.1) != base
    assert fingerprint(A, sketch="gaussian") != base
    assert fingerprint(A, sketch_size=32) != base
    assert fingerprint(A.astype(jnp.float32)) != base


def test_digest_memo_hits_by_identity():
    A = _A()
    d1 = digest_array(A)
    d2 = digest_array(A)
    assert d1 == d2
    assert digest_array(jnp.array(A)) == d1  # same bytes, fresh object


def test_inplace_mutation_changes_fingerprint():
    """A writable numpy A mutated in place must NOT hit a stale memo —
    the old digest would serve the old matrix's cached factor."""
    import numpy as np

    A = np.asarray(_A()).copy()
    fp1 = fingerprint(A)
    orig = A[0, 0]
    A[0, 0] = orig + 1.0
    fp2 = fingerprint(A)
    assert fp1 != fp2
    # restore the exact bytes: (a + 1) - 1 need not round back to a
    A[0, 0] = orig
    assert fingerprint(A) == fp1


def test_readonly_numpy_is_memoized():
    import numpy as np

    A = np.asarray(_A()).copy()
    A.setflags(write=False)
    assert digest_array(A) == digest_array(A)
    assert fingerprint(A) == fingerprint(A)


def test_tenant_namespaces_tokens():
    A, A2 = _A(), _A(seed=1)
    # Same token from two tenants: PRIVATE namespaces, no collision even
    # for different matrices of the same shape/dtype/config.
    fa = fingerprint(A, token="v1", tenant="alice")
    fb = fingerprint(A2, token="v1", tenant="bob")
    assert fa != fb
    assert fingerprint(A, token="v1", tenant="alice") == fa
    # tenant= without a token is a no-op: content digests stay shared.
    assert fingerprint(A, tenant="alice") == fingerprint(A)
    # the operator path namespaces too
    op = linop.CustomOperator(
        matvec_fn=lambda x: A @ x, rmatvec_fn=lambda y: A.T @ y,
        op_shape=A.shape, op_dtype=A.dtype,
    )
    assert (fingerprint(op, token="v1", tenant="alice")
            != fingerprint(op, token="v1", tenant="bob"))


def test_bcoo_fingerprint():
    A = _A()
    M = jsparse.BCOO.fromdense(jnp.where(jnp.abs(A) > 1.0, A, 0.0))
    fp = fingerprint(M)
    assert fp.kind == "bcoo"
    M2 = jsparse.BCOO.fromdense(jnp.where(jnp.abs(A) > 1.0, A + 2.0, 0.0))
    assert fingerprint(M2) != fp


def test_operator_requires_token():
    A = _A()
    op = linop.CustomOperator(
        matvec_fn=lambda x: A @ x, rmatvec_fn=lambda y: A.T @ y,
        op_shape=A.shape, op_dtype=A.dtype,
    )
    with pytest.raises(ValueError, match="token"):
        fingerprint(op)
    fp = fingerprint(op, token="model-v3")
    assert fp.kind == "operator"
    assert fingerprint(op, token="model-v3") == fp
    assert fingerprint(op, token="model-v4") != fp


def test_token_overrides_digest_for_arrays():
    A = _A()
    assert fingerprint(A, token="t1") == fingerprint(_A(seed=1), token="t1")


def test_short_is_human_readable():
    s = fingerprint(_A(), reg=0.5).short()
    assert "50x7" in s and "reg=0.5" in s


def test_fingerprint_is_frozen():
    fp = fingerprint(_A())
    assert isinstance(fp, Fingerprint)
    with pytest.raises(Exception):
        fp.kind = "other"
