"""The Pallas kernels at the shapes the chip runs them (GPT-2-small widths,
the serving defaults), as ``name -> (fn, abstract args)`` — shared by the
cross-lowering test and its Mosaic subprocess (tests/mosaic_compile_proc.py).
Every kernel is built with ``interpret=False``: this is the program the TPU
gets, not the interpreter's."""

import jax
import jax.numpy as jnp

B, H, D = 8, 12, 64          # decode rows, heads, head dim
PT, PAGES, TABLE = 16, 513, 64  # page tokens, arena pages, table width


def _sds(shape, dtype):
    return jax.ShapeDtypeStruct(shape, jnp.dtype(dtype))


def kernel_cases():
    from kubeml_tpu.ops.flash_attention import flash_attention
    from kubeml_tpu.ops.gated_delta import gdn_update
    from kubeml_tpu.ops import hyper_connection as hc
    from kubeml_tpu.ops.grouped_matmul import grouped_matmul
    from kubeml_tpu.ops.int8_matmul import int8_matmul
    from kubeml_tpu.ops.mla_attention import latent_row_width, mla_attn
    from kubeml_tpu.ops.paged_attention import kv_row_width, paged_attention
    from kubeml_tpu.ops.ssm import ssm_update

    cases = {}
    table = _sds((B, TABLE), jnp.int32)
    pos = _sds((B,), jnp.int32)
    scales = _sds((PAGES, H), jnp.float32)
    # L: a decode step, a speculative verify window, one query tile of
    # suffix prefill, and a prefill that spans several tiles
    for L in (1, 5, 128, 512):
        for name, q_dt, kv_dt in (("bf16", jnp.bfloat16, jnp.bfloat16),
                                  ("f32", jnp.float32, jnp.float32),
                                  ("int8", jnp.bfloat16, jnp.int8)):
            q = _sds((B, L, H, D), q_dt)
            # token rows of K‖V: 12 heads of 64 twice over, 1,536 lanes
            arena = _sds((PAGES, PT, kv_row_width(H, D)), kv_dt)
            if name == "int8":
                fn = lambda q, kv, t, p, ks, vs: paged_attention(
                    q, kv, t, p, interpret=False, k_scale=ks, v_scale=vs)
                args = (q, arena, table, pos, scales, scales)
            else:
                fn = lambda q, kv, t, p: paged_attention(
                    q, kv, t, p, interpret=False)
                args = (q, arena, table, pos)
            cases[f"paged_attention-{name}-L{L}"] = (fn, args)
    # GPT-2 XL's odd head count, 25 of 64 on 4 rows (rows of 3,200 lanes: a
    # head's V starts at lane 1,600 + 64 h), a decode step and one tile of
    # a one-row admit; gpt2-large's 20 of 64 on 8 rows the same. The decode
    # step (L 1) takes the kernel's decode body, 16 pages a program
    for tag, heads, slab, pool in (("xl", 25, 4, 257), ("large", 20, 8, 513)):
        for L, nrows in ((1, slab), (128, 1)):
            cases[f"paged_attention-gpt2-{tag}-bf16-L{L}"] = (
                lambda q, kv, t, p: paged_attention(q, kv, t, p,
                                                    interpret=False),
                (_sds((nrows, L, heads, D), jnp.bfloat16),
                 _sds((pool, PT, kv_row_width(heads, D)), jnp.bfloat16),
                 _sds((nrows, TABLE), jnp.int32), _sds((nrows,), jnp.int32)))
    # the tile body at the cells' admit shapes (PR 40's sweep on the chip):
    # one row of 1,024 positions under 64 pages, 8 query tiles by 4 chunks
    # of 16 pages, at 20 and at 25 heads; gpt2-large.chat's 1 x 512 under 32
    # pages; and the 512 positions after a prefix hit 512 deep, whose
    # prefix chunks take the unmasked branch. (Falcon-H1's 1 x 128 under 8
    # pages is the L128 case below.)
    for tag, heads, pool, L, width in (
            ("large", 20, 513, 1024, 64), ("xl", 25, 257, 1024, 64),
            ("large", 20, 513, 512, 32), ("large-suffix", 20, 513, 512, 64)):
        cases[f"paged_attention-gpt2-{tag}-bf16-admit-L{L}-P{width}"] = (
            lambda q, kv, t, p: paged_attention(q, kv, t, p,
                                                interpret=False),
            (_sds((1, L, heads, D), jnp.bfloat16),
             _sds((pool, PT, kv_row_width(heads, D)), jnp.bfloat16),
             _sds((1, width), jnp.int32), _sds((1,), jnp.int32)))
    # Falcon-H1-34B's published shapes: 20 query heads on 4 K/V heads of
    # 128 over a 32-row slab (a decode step and one prefill tile), and the
    # mixer's state update, 32 heads of [256, 128] float32 in 2 groups
    rows, hq, hkv, d = 32, 20, 4, 128
    # (the step at the cell's widest and narrowest table: 16 and 8 pages a
    # program of the decode body)
    for L, width, tag in ((1, 32, ""), (1, 8, "-P8"), (128, 8, "")):
        cases[f"paged_attention-gqa-bf16-L{L}{tag}"] = (
            lambda q, kv, t, p: paged_attention(q, kv, t, p, kv_heads=4,
                                                interpret=False),
            (_sds((rows, L, hq, d), jnp.bfloat16),
             _sds((2049, PT, kv_row_width(hkv, d)), jnp.bfloat16),
             _sds((rows, width), jnp.int32), _sds((rows,), jnp.int32)))
    cases["ssm_update-falcon-h1-34b"] = (
        lambda s, x, dt, a, b, c: ssm_update(s, x, dt, a, b, c,
                                             interpret=False),
        (_sds((rows, 32, 256, 128), jnp.float32),
         _sds((rows, 32, 128), jnp.float32), _sds((rows, 32), jnp.float32),
         _sds((32,), jnp.float32), _sds((rows, 2, 256), jnp.float32),
         _sds((rows, 2, 256), jnp.float32)))
    # GLM-4.7-Flash's published shapes: the latent page walk of a decode
    # step (20 heads against pages of 16 x (512 + 64), 32 rows, both table
    # widths the cell reaches) and the experts' grouped products (64 experts
    # of 2048 x 1536; a step's 128 assignments, a prefill's 8192)
    for width in (128, 256):
        cases[f"mla_attn-glm-4.7-flash-P{width}"] = (
            lambda q, a, t, p: mla_attn(q, a, t, p, value_dim=512,
                                        scale=1 / 16, interpret=False),
            (_sds((rows, 20, 576), jnp.bfloat16),
             _sds((8193, PT, latent_row_width(576)), jnp.bfloat16),
             _sds((rows, width), jnp.int32), _sds((rows,), jnp.int32)))
    for m in (128, 8192):
        cases[f"moe_experts-gated-m{m}"] = (
            lambda x, w, u, g: grouped_matmul(x, w, g, u, kernel=True,
                                              interpret=False),
            (_sds((m, 2048), jnp.bfloat16),
             _sds((64, 2048, 1536), jnp.bfloat16),
             _sds((64, 2048, 1536), jnp.bfloat16), _sds((64,), jnp.int32)))
        cases[f"moe_experts-down-m{m}"] = (
            lambda x, w, g: grouped_matmul(x, w, g, kernel=True,
                                           interpret=False),
            (_sds((m, 1536), jnp.bfloat16),
             _sds((64, 1536, 2048), jnp.bfloat16), _sds((64,), jnp.int32)))
    # Xing4.0-29B-A4B's published shapes: the latent walk at 32 heads of 128
    # + 64 (YaRN's softmax scale), the experts at 3584 x 1024, and the
    # residual path's two kernels over four streams of 3584 in bfloat16, a
    # 2,048-position admit and a 32-row step
    cases["mla_attn-xing4.0-P128"] = (
        lambda q, a, t, p: mla_attn(q, a, t, p, value_dim=512,
                                    scale=0.14467962580, interpret=False),
        (_sds((rows, 32, 576), jnp.bfloat16),
         _sds((8193, PT, latent_row_width(576)), jnp.bfloat16),
         _sds((rows, 128), jnp.int32), _sds((rows,), jnp.int32)))
    for m in (128, 8192):
        cases[f"moe_experts-xing4.0-gated-m{m}"] = (
            lambda x, w, u, g: grouped_matmul(x, w, g, u, kernel=True,
                                              interpret=False),
            (_sds((m, 3584), jnp.bfloat16),
             _sds((64, 3584, 1024), jnp.bfloat16),
             _sds((64, 3584, 1024), jnp.bfloat16), _sds((64,), jnp.int32)))
        cases[f"moe_experts-xing4.0-down-m{m}"] = (
            lambda x, w, g: grouped_matmul(x, w, g, kernel=True,
                                           interpret=False),
            (_sds((m, 1024), jnp.bfloat16),
             _sds((64, 1024, 3584), jnp.bfloat16), _sds((64,), jnp.int32)))
    # LongCat-Flash's published shapes (ISSUE 41): the latent walk at 64
    # heads of 128 + 64 on the 512 + 64 latent, 64 rows, the three table
    # widths the cell reaches; the grouped products over one chip's share,
    # 16 groups of 6144 x 2048, at a step's 64 x 12 and a 512-position
    # admit's 512 x 12 assignment rows, of which a group holds 0, 1 or 2 and
    # most belong to none (group sizes are the kernel's data, not its shape)
    for width in (32, 64, 128):
        cases[f"mla_attn-longcat-flash-P{width}"] = (
            lambda q, a, t, p: mla_attn(q, a, t, p, value_dim=512,
                                        scale=192 ** -0.5, interpret=False),
            (_sds((64, 64, 576), jnp.bfloat16),
             _sds((8193, PT, latent_row_width(576)), jnp.bfloat16),
             _sds((64, width), jnp.int32), _sds((64,), jnp.int32)))
    for m in (768, 6144):
        cases[f"moe_experts-longcat-flash-gated-m{m}"] = (
            lambda x, w, u, g: grouped_matmul(x, w, g, u, kernel=True,
                                              interpret=False),
            (_sds((m, 6144), jnp.bfloat16),
             _sds((16, 6144, 2048), jnp.bfloat16),
             _sds((16, 6144, 2048), jnp.bfloat16), _sds((16,), jnp.int32)))
        cases[f"moe_experts-longcat-flash-down-m{m}"] = (
            lambda x, w, g: grouped_matmul(x, w, g, kernel=True,
                                           interpret=False),
            (_sds((m, 2048), jnp.bfloat16),
             _sds((16, 2048, 6144), jnp.bfloat16), _sds((16,), jnp.int32)))
    streams = hc.HCConfig(mult=4)
    for positions in (2048, 32):
        cases[f"hc_pre-xing4.0-T{positions}"] = (
            lambda x, phi, alpha, bias: hc._pre_call(
                x, {"phi": phi, "alpha": alpha, "bias": bias}, streams,
                False),
            (_sds((positions, 4 * 3584), jnp.bfloat16),
             _sds((4 * 3584, 24), jnp.bfloat16), _sds((3,), jnp.bfloat16),
             _sds((24,), jnp.bfloat16)))
        cases[f"hc_post-xing4.0-T{positions}"] = (
            lambda x, y, co: hc._post_call(x, y, co, 4, False),
            (_sds((positions, 4 * 3584), jnp.bfloat16),
             _sds((positions, 3584), jnp.bfloat16),
             _sds((positions, 20), jnp.float32)))
    # the MLP up-projection and the lm_head (vocab 50257: not a tile multiple)
    for K, N in ((768, 3072), (768, 50257)):
        cases[f"int8_matmul-{K}x{N}"] = (
            lambda x, q, s: int8_matmul(x, q, s, interpret=False),
            (_sds((B, K), jnp.bfloat16), _sds((K, N), jnp.int8),
             _sds((1, N), jnp.float32)))
    qkv = _sds((1, 2048, H, D), jnp.bfloat16)
    flash = lambda q, k, v: flash_attention(q, k, v, causal=True,
                                            interpret=False)
    cases["flash_attention-fwd"] = (flash, (qkv, qkv, qkv))
    cases["flash_attention-bwd"] = (
        jax.grad(lambda q, k, v: flash(q, k, v).astype(jnp.float32).sum(),
                 argnums=(0, 1, 2)),
        (qkv, qkv, qkv))
    # MiMo-V2-Flash's published shapes (ISSUE 44): 64 query heads of 192
    # with V heads of 128 (a K head as 128 + a tail of 64) on 4 K/V heads in a
    # full layer and 8 under a window of 128 with a sink; 64 rows. A decode
    # step of a full layer under both table widths the cell reaches (16
    # pages a program), of a window layer over the rows' rings of 10 pages
    # (one program a row); and the 4,096-position admit of each kind, one
    # K/V head a program (64 heads' carries would be 24 MiB): the full
    # layer's through the row's 256-page table, the window layer's over the
    # bucket's own 256 pages
    mimo = dict(v_head_dim=128, value_scale=0.707, interpret=False)
    for tag, hkv, window, width, nrows, L in (
            ("full-L1-P256", 4, 0, 256, 64, 1),
            ("full-L1-P288", 4, 0, 288, 64, 1),
            ("window-L1-ring10", 8, 128, 10, 64, 1),
            ("full-admit-L4096", 4, 0, 256, 1, 4096),
            ("window-admit-L4096", 8, 128, 256, 1, 4096)):
        pool = 18433 if not window else 641 if L == 1 else 257
        args = (_sds((nrows, L, 64, 192), jnp.bfloat16),
                _sds((pool, PT, kv_row_width(hkv, 192, 128)), jnp.bfloat16),
                _sds((nrows, width), jnp.int32), _sds((nrows,), jnp.int32))
        if window:
            cases[f"paged_attention-mimo-v2-{tag}"] = (
                lambda q, kv, t, p, s, h=hkv, w=window: paged_attention(
                    q, kv, t, p, kv_heads=h, window=w, sink=s, **mimo),
                args + (_sds((64,), jnp.float32),))
        else:
            cases[f"paged_attention-mimo-v2-{tag}"] = (
                lambda q, kv, t, p, h=hkv: paged_attention(
                    q, kv, t, p, kv_heads=h, **mimo), args)
    # Olmo-Hybrid-7B's published shapes (ISSUE 48): the gated-delta state
    # of 30 heads of [96, 192] float32, stored two heads side by side as 15
    # of [96, 384], on a 96-row slab; and the two full layers' walk at 30
    # K/V heads of 128 (rows of 7,680 lanes), a decode step under the three
    # table widths the cell reaches and the 512-position admit
    cases["gdn_update-olmo-hybrid-7b"] = (
        lambda s, q, k, v, g, b: gdn_update(s, q, k, v, g, b,
                                            interpret=False),
        (_sds((96, 15, 96, 384), jnp.float32),
         _sds((96, 30, 96), jnp.float32), _sds((96, 30, 96), jnp.float32),
         _sds((96, 30, 192), jnp.float32), _sds((96, 30), jnp.float32),
         _sds((96, 30), jnp.float32)))
    for tag, nrows, L, width in (("L1-P32", 96, 1, 32), ("L1-P64", 96, 1, 64),
                                 ("L1-P96", 96, 1, 96),
                                 ("admit-L512", 1, 512, 32)):
        cases[f"paged_attention-olmo-hybrid-{tag}"] = (
            lambda q, kv, t, p: paged_attention(q, kv, t, p,
                                                interpret=False),
            (_sds((nrows, L, 30, 128), jnp.bfloat16),
             _sds((9217, PT, kv_row_width(30, 128)), jnp.bfloat16),
             _sds((nrows, width), jnp.int32), _sds((nrows,), jnp.int32)))
    # Kimi-Linear-48B-A3B's published shapes (ISSUE 51): the delta rule
    # gated per key channel, 32 heads of [128, 128] float32 (one whole lane
    # row a head: nothing is packed) on a 128-row slab, a gate of 128 values
    # a head; and the two latent layers' walk at 32 heads over rows of 640
    # lanes, a decode step under the three table widths the cell reaches
    cases["kda_update-kimi-linear-48b-a3b"] = (
        lambda s, q, k, v, g, b: gdn_update(s, q, k, v, g, b,
                                            interpret=False),
        (_sds((128, 32, 128, 128), jnp.float32),
         _sds((128, 32, 128), jnp.float32), _sds((128, 32, 128), jnp.float32),
         _sds((128, 32, 128), jnp.float32),
         _sds((128, 32, 128), jnp.float32), _sds((128, 32), jnp.float32)))
    for width in (64, 128, 192):
        cases[f"mla_attn-kimi-linear-L1-P{width}"] = (
            lambda q, a, t, p: mla_attn(q, a, t, p, value_dim=512,
                                        scale=192 ** -0.5, interpret=False),
            (_sds((128, 32, latent_row_width(576)), jnp.bfloat16),
             _sds((24577, PT, latent_row_width(576)), jnp.bfloat16),
             _sds((128, width), jnp.int32), _sds((128,), jnp.int32)))
    return cases
