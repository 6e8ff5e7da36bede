"""The scope reader on a small program compiled here: an operation's
rendered name counts for a scope only when no instruction outside the
scope renders the same."""
import jax
import jax.numpy as jnp

from chipbench import hlo_scopes


def _text():
    def f(x, w):
        with jax.named_scope("inner.scan"):
            def body(c, r):
                return jnp.tanh(c @ w) + r, None
            y, _ = jax.lax.scan(body, x, jnp.ones((3,) + x.shape))
        with jax.named_scope("outer"):
            return jnp.sum(jnp.exp(y) @ w)
    x = jnp.ones((8, 16))
    w = jnp.ones((16, 16))
    return jax.jit(jax.grad(f)).lower(x, w).compile().as_text()


def test_names_under_a_scope_include_its_loop_and_not_the_rest():
    text = _text()
    names, stats = hlo_scopes.scope_op_names(text, "inner.scan")
    assert names and stats["op_names"] == len(names)
    assert 0 < stats["unambiguous_instructions"] <= stats["scope_instructions"]
    outer, _ = hlo_scopes.scope_op_names(text, "outer")
    assert not set(names) & set(outer)
    assert any(n.startswith("while") for n in names), names
    none, stats = hlo_scopes.scope_op_names(text, "no.such.scope")
    assert none == [] and stats["scope_instructions"] == 0


def test_shared_name_is_left_out():
    text = """HloModule m

%body (p: f32[4]) -> f32[4] {
  %p = f32[4]{0} parameter(0)
  ROOT %add.2 = f32[4]{0} add(%p, %p), metadata={op_name="jit(f)/a.scope/add"}
}

ENTRY %main (x: f32[4]) -> f32[4] {
  %x = f32[4]{0} parameter(0)
  %add.1 = f32[4]{0} add(%x, %x), metadata={op_name="jit(f)/add"}
  %mul.1 = f32[4]{0} multiply(%x, %x), metadata={op_name="jit(f)/a.scope/mul"}
  ROOT %call.1 = f32[4]{0} call(%mul.1), to_apply=%body
}
"""
    names, stats = hlo_scopes.scope_op_names(text, "a.scope")
    assert names == ["mul f32[4]"]
    assert stats == {"scope_instructions": 2, "unambiguous_instructions": 1,
                     "op_names": 1}
