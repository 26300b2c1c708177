"""Shape inventory of every Pallas-kernel call site the fused
ResNet-50 bench + quantized/LM paths hit — shared by the on-chip smoke
(chip_smoke.py's kernel phase), the offline deviceless AOT check
(tools/tpu_aot_check.py) and the in-process gate (tests/test_tpu_aot.py)
so they can never drift apart."""

BATCH = 256

# stride-1 3x3 convs in ResNet-50 bottlenecks: (H, W, Cin, Cout)
CONV3 = [(56, 56, 64, 64), (28, 28, 128, 128),
         (14, 14, 256, 256), (7, 7, 512, 512)]

# conv3 dgrad kernel (BIGDL_TPU_FUSED_CONV3_BWD): smallest-channel
# shapes, where tiling surprises live
CONV3_BWD = [(56, 56, 64, 64), (28, 28, 128, 128)]

# 1x1 convs as matmuls: (M, K, N) for every bottleneck projection
MATMUL = [(BATCH * 56 * 56, 64, 64), (BATCH * 56 * 56, 64, 256),
          (BATCH * 56 * 56, 256, 64), (BATCH * 28 * 28, 256, 128),
          (BATCH * 28 * 28, 128, 512), (BATCH * 28 * 28, 512, 128),
          (BATCH * 14 * 14, 512, 256), (BATCH * 14 * 14, 256, 1024),
          (BATCH * 14 * 14, 1024, 256), (BATCH * 7 * 7, 1024, 512),
          (BATCH * 7 * 7, 512, 2048), (BATCH * 7 * 7, 2048, 512)]

# int8 s8 x s8 -> s32 matmul (transformer FFN shapes, quant_bench),
# plus the int8 KV-cache score shape (Tq, D, L): the speculative
# verify's QK^T against a quantized paged pool at a 4096-token extent
# (ops/paged_kv.int8_scores).  Tq is padded to the kernel's minimum
# 8-row tile.
INT8 = [(4096, 768, 3072), (4096, 3072, 768), (8, 128, 4096)]

# flash attention (B, H, T, D): the long-standing smoke shape, and the
# LM train cell's own (tools/lm_bench.py LM_DEFAULTS: batch 8, 12 heads
# of 64 over hidden 768, seq 2048)
FLASH = [(1, 2, 1024, 128), (8, 12, 2048, 64)]

# paged decode attention (S, H, D, Q, M): the decode cell's tick — 32
# slots, 12 heads of 64, pages of 16 tokens, 128 pages a slot
# (ops/pallas/paged_attention.py; benchmark/traffic/decode-steady.json)
PAGED_ATTN = [(32, 12, 64, 16, 128)]

# absorbed decode attention over latent pages (S, H, C, Vw, Q, M): the
# latent cell's tick - 32 slots, 64 heads sharing one 640-lane row (576
# numbers), values the first 512 lanes, pages of 16, 512 pages a slot
# (ops/pallas/latent_attention.py; benchmark/traffic/decode-longprompt.json)
LATENT_PAGED_ATTN = [(32, 64, 640, 512, 16, 512)]

# a prompt chunk against the cached extent (B, H, T, S, D): 2048
# queries at an offset, 8192 expanded keys, head width 192
# (ops/pallas/flash_attention.prefix_flash_attention)
FLASH_PREFIX = [(1, 64, 2048, 8192, 192)]

# the routed experts' buffer (M, K, N, groups, rows an expert expects):
# a 2048-token prefill chunk's three grouped products
# (ops/pallas/grouped_matmul.py via nn/routed.py) - the latent cell's
# likely buffer over its 16 held experts, 64 rows each expected, and the
# window cell's full buffer over all 128, 128 each - and a 32-row tick's
GROUPED_MATMUL = [(4096, 7168, 2048, 16, 64), (4096, 2048, 7168, 16, 64),
                  (16384, 2048, 1024, 128, 128),
                  (16384, 1024, 2048, 128, 128),
                  # the hybrid cell's two relu**2 products in the 1024
                  # latent: 2048 x 22 rows over 128 held experts of 512,
                  # 88 each expected (the full buffer: more than the
                  # likely one lands at a quarter of the experts)
                  (45056, 1024, 2688, 128, 88),
                  (45056, 2688, 1024, 128, 88),
                  # the tick's 32 rows x 8 experts: the latent cell's
                  # 256 over its 16 held of 256 (1 expected, about 16
                  # land) and the window cell's over all 128 (2 each)
                  (256, 7168, 2048, 16, 1), (256, 2048, 7168, 16, 1),
                  (256, 2048, 1024, 128, 2), (256, 1024, 2048, 128, 2)]

# the hybrid Mamba-2 cell's geometry (slots, max_len, page, prompt chunk;
# benchmark/traffic/decode-reasoning-closed128.json): the tick steps 128
# state blocks a Mamba-2 layer and reads the attention layer's pages
# through paged_attn (32 query heads over 2 K/V heads of 128)
HYBRID_DECODE = (128, 8192, 64, 2048)

# the Granite 4.0-H cell's geometry (slots, max_len, page, prompt chunk,
# largest prefill batch; benchmark/traffic/decode-chat-closed64.json):
# the tick steps 64 state blocks in each of 36 Mamba-2 layers and reads
# four attention layers' pages through paged_attn (32 query heads over 8
# K/V heads of 64); a bucketed prefill runs 4 prompts of 2048
GRANITE_DECODE = (64, 4096, 64, 2048, 4)

# ---------------------------------------------------------------------
# cached-decode serving shapes (serving/decode.py, docs/decoding.md):
# the slot-grid geometry shared by bench.py --decode-ab, the
# `decode_step` graft-lint target, and tools/serving_aot_check.py
# --decode, so the deviceless-proven shapes can never drift from what
# the engine actually compiles.
# ---------------------------------------------------------------------
DECODE_SLOTS = 4
DECODE_MAX_LEN = 160
DECODE_PROMPT_BUCKETS = (8, 16)
DECODE_PREFILL_BATCH = (1, 2, 4)
# the bench/lint decode LM config (nn.Transformer kwargs)
DECODE_MODEL = dict(vocab_size=32, hidden_size=48, num_heads=4,
                    filter_size=96, num_layers=2, dropout=0.0,
                    causal=True)
# decode-step attention shape (B=slots, H, Tq=1, Tmax) of the DENSE
# cache: Tq=1 cannot tile the flash kernel's q block, so the dense
# decode core is the mask-carrying dot_product_attention.  The paged
# tick's Tq=1 attention is a kernel of its own (PAGED_ATTN above).
DECODE_ATTN = (DECODE_SLOTS, 4, 1, DECODE_MAX_LEN)
# production-decode extensions (ISSUE 14): paged KV pool geometry,
# chunked prefill, and the speculative draft.  DECODE_PAGES is the
# worst-case pool (slots * pages-per-slot + trash page 0) — bench's
# paged arm runs 2x slots against this same budget to demonstrate
# capacity, tools/serving_aot_check.py --decode compiles the paged
# tick/write/reset at exactly these shapes.
DECODE_PAGE = 16
DECODE_PAGES = DECODE_SLOTS * (DECODE_MAX_LEN // DECODE_PAGE) + 1
DECODE_CHUNK = 16
DECODE_DRAFT_K = 3
# the speculative draft LM: same vocab/width family, half the depth
DECODE_DRAFT_MODEL = dict(vocab_size=32, hidden_size=48, num_heads=4,
                          filter_size=96, num_layers=1, dropout=0.0,
                          causal=True)
