"""Build the evidence embedding index for open retrieval (REALM/ORQA).

TPU-native equivalent of the reference's indexing entry
(ref: tools/create_doc_index.py + megatron/indexer.py): run the biencoder's
context tower over a DPR-style evidence TSV and persist the
{row_id: embedding} store that tasks/main.py --task NQ searches.

  python tools/create_doc_index.py --load <biencoder_ckpt> \
      --evidence_data_path psgs_w100.tsv --embedding_path evidence.npz \
      --tokenizer_type BertWordPieceLowerCase --vocab_file vocab.txt

Multi-host: run one process per shard with --shard i --num_shards N, then
merge with --merge.
"""
from __future__ import annotations

import argparse
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from megatron_tpu.utils.compile_cache import ensure_compile_cache


def main(argv=None):
    ensure_compile_cache()
    p = argparse.ArgumentParser("create_doc_index", description=__doc__)
    p.add_argument("--load", required=True,
                   help="biencoder checkpoint root")
    p.add_argument("--evidence_data_path", required=True)
    p.add_argument("--embedding_path", required=True)
    p.add_argument("--tokenizer_type", default="BertWordPieceLowerCase")
    p.add_argument("--vocab_file", default=None)
    p.add_argument("--merge_file", default=None)
    p.add_argument("--tokenizer_model", default=None)
    p.add_argument("--retriever_seq_length", type=int, default=256)
    p.add_argument("--indexer_batch_size", type=int, default=128)
    p.add_argument("--indexer_log_interval", type=int, default=10)
    p.add_argument("--ict_head_size", type=int, default=128)
    p.add_argument("--biencoder_shared_query_context_model",
                   action="store_true")
    p.add_argument("--shard", type=int, default=0)
    p.add_argument("--num_shards", type=int, default=1)
    p.add_argument("--merge", action="store_true",
                   help="merge shard files written by previous runs and "
                        "exit")
    # model shape fallback when the checkpoint has no config
    p.add_argument("--num_layers", type=int, default=12)
    p.add_argument("--hidden_size", type=int, default=768)
    p.add_argument("--num_attention_heads", type=int, default=12)
    args = p.parse_args(argv)

    from megatron_tpu.data.realm_index import OpenRetrievalDataStore

    if args.merge:
        store = OpenRetrievalDataStore(args.embedding_path,
                                       load_from_path=False)
        store.merge_shards_and_save()
        print(f"merged {len(store)} block embeddings -> "
              f"{args.embedding_path}")
        return 0

    from megatron_tpu.data.orqa_dataset import OpenRetrievalEvidenceDataset
    from megatron_tpu.data.tokenizers import build_tokenizer
    from megatron_tpu.indexer import IndexBuilder
    from tasks.main import load_biencoder

    tokenizer = build_tokenizer(
        args.tokenizer_type, vocab_file=args.vocab_file,
        merge_file=args.merge_file, tokenizer_model=args.tokenizer_model)
    params, mcfg = load_biencoder(args, tokenizer.vocab_size,
                                  args.retriever_seq_length)
    evidence = OpenRetrievalEvidenceDataset(
        args.evidence_data_path, tokenizer, args.retriever_seq_length)
    builder = IndexBuilder(
        params, mcfg, evidence, embedding_path=args.embedding_path,
        batch_size=args.indexer_batch_size, shard=args.shard,
        num_shards=args.num_shards,
        log_interval=args.indexer_log_interval)
    store = builder.build_and_save_index()
    print(f"indexed {len(store)} evidence blocks"
          + (f" (shard {args.shard}/{args.num_shards})"
             if args.num_shards > 1 else ""))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
