"""Flagship model zoo built on the graph API (reference keeps these in
``examples/transformers/*``; they live in-package here so benchmarks, the
graft entry and examples share one implementation)."""
from .bert import (BertConfig, bert_model, bert_pretrain_graph,
                   bert_pooler, bert_classify_graph)
from .gpt2 import (GPT2Config, gpt2_model, gpt2_lm_graph,
                   gpt2_decode_graph, gpt2_decode_chunked_graph,
                   synthetic_lm_batch)
from .t5 import (T5Config, t5_encoder, t5_decoder, t5_seq2seq_graph,
                 synthetic_seq2seq_batch)
from .vit import (ViTConfig, vit_model, vit_classify_graph,
                  synthetic_image_batch)
from .swin import SwinConfig, swin_model, swin_classify_graph
from .transformer import (TransformerConfig, transformer_graph,
                          synthetic_copy_batch)
from .bart import BartConfig, bart_seq2seq_graph
from .longformer import (LongformerConfig, longformer_model,
                         longformer_mlm_graph, longformer_attention_mask)
from .reformer import (ReformerConfig, reformer_model, reformer_lm_graph,
                       lsh_attention)
from .transfoxl import TransfoXLConfig, transfoxl_model, transfoxl_lm_graph
from .clip import CLIPConfig, clip_graph, clip_vision_tower, clip_text_tower
from .mae import MAEConfig, mae_pretrain_graph, synthetic_mae_batch
from .bigbird import (BigBirdConfig, bigbird_model, bigbird_mlm_graph,
                      bigbird_attention_mask)
from .xlnet import (XLNetConfig, xlnet_model, xlnet_plm_graph,
                    perm_masks_from_order, synthetic_plm_batch)
from .phi4flash import (Phi4FlashConfig, phi4flash_decode_graph,
                        phi4flash_decode_chunked_graph, phi4flash_lm_graph)
from .solar_open2 import (SolarOpen2Config, solar_open2_decode_graph,
                          solar_open2_decode_chunked_graph,
                          solar_open2_lm_graph)
from .glm4_moe_lite import (Glm4MoeLiteConfig, glm4_moe_lite_decode_graph,
                            glm4_moe_lite_decode_chunked_graph,
                            glm4_moe_lite_lm_graph)
from .minicpm_sala import (MiniCPMSALAConfig, minicpm_sala_decode_graph,
                           minicpm_sala_decode_chunked_graph,
                           minicpm_sala_lm_graph)
from .granite_hybrid import (GraniteHybridConfig, granite_hybrid_decode_graph,
                             granite_hybrid_decode_chunked_graph,
                             granite_hybrid_lm_graph)
