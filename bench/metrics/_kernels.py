"""Names the per-layer readers share: the kernels of the training step as
the HLO names them, and the programs (jitted stages) of the engine."""
ATTENTION = ("attn_fwd", "attn_bwd_kv", "attn_bwd_q")
NEGATIVES = ("neg_fused_fwd", "neg_fused_bwd")
SPARSE = ("lookup_wscatter", "lookup_runsum")
NAMED = ATTENTION + NEGATIVES + SPARSE + ("lookup_gather",)
DENSE_PROGRAM = "jit_dense_fwd_bwd"
SPARSE_PROGRAMS = ("jit_emb_bwd", "jit_sparse_apply")
