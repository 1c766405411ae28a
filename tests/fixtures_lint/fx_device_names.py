"""Known-bad device-scope / kernel-name fixture against
fx_names_registry.py. AST-parsed only."""

jax = pl = None  # parsed, never run


def _pair_call(kernel, *, name):
    return pl.pallas_call(kernel, name=name)       # clean: not a literal


@jax.named_scope("fx_scope")                       # clean (decorator form)
def step(kind, kernel):
    with jax.named_scope("fx_scoop"):              # line 13: DTL041
        pass
    with jax.named_scope(f"fx_attn.{kind}"):       # clean: head matches
        pass
    with jax.named_scope(f"fx_ffn.{kind}"):        # line 17: DTL041 (head)
        pass
    pl.pallas_call(kernel, name="fx_kernel_fwd")   # clean
    pl.pallas_call(kernel, name="fx_kernel_bwd")   # line 20: DTL041
    pl.pallas_call(kernel)                         # line 21: DTL041 (unnamed)
    _pair_call(kernel, name="fx_kernel_dq")        # line 22: DTL041 (wrapper)
    jax.named_scope("fx_kernel_fwd")               # line 23: DTL041 (kind)
