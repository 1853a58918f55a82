"""whisper-large-v3 [audio] — encoder-decoder recognizer [arXiv:2212.04356].

Widths from the published ``openai/whisper-large-v3`` config.json: a conv
front end (conv1d 128 mel bins -> 1280, kernel 3; conv1d 1280 -> 1280,
kernel 3, stride 2; exact GELU after each) over 3000 log-mel frames (30 s
at a 10 ms hop), 1500 sinusoidal encoder positions, 32 pre-LayerNorm
encoder and 32 decoder layers of d_model 1280, 20 heads of 64, FFN 5120
with exact GELU, 448 learned decoder positions, a 51,866-token vocabulary
with the output head tied to the embedding; q, v and out projections
carry a bias, k none.

``long_500k`` is skipped: the decoder holds 448 learned positions, so a
262k-token transcript decode is outside the model.
"""
from repro.configs.base import ArchConfig, register

WHISPER_LARGE_V3 = register(
    ArchConfig(
        name="whisper-large-v3",
        family="encdec",
        n_layers=32,           # decoder layers
        n_enc_layers=32,       # encoder layers
        d_model=1280,
        n_heads=20,
        n_kv_heads=20,         # MHA
        d_ff=5120,
        vocab=51866,
        head_dim=64,
        rope_theta=0.0,        # sinusoidal encoder, learned decoder positions
        norm="layernorm",
        act="gelu",
        use_bias=True,
        tie_embeddings=True,
        n_mels=128,
        n_audio_ctx=1500,
        n_text_ctx=448,
        citation="arXiv:2212.04356 (Whisper); openai/whisper-large-v3 "
                 "config.json",
        skip_shapes=("long_500k",),
        train_strategy="sd_psgd",
        n_learners=16,
        microbatches=4,
    )
)
