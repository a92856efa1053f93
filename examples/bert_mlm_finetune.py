"""BERT MLM fine-tune — tokenize a corpus, build MLM batches, fine-tune
(BASELINE workload #4; reference: ``BertIterator`` + samediff TF import)."""

from deeplearning4j_tpu.models.bert import BertConfig, BertForMaskedLM
from deeplearning4j_tpu.nlp import (BertIterator, BertWordPieceTokenizer,
                                    CollectionSentenceProvider, build_vocab)
from deeplearning4j_tpu.train import Adam

CORPUS = [
    "the model predicts masked words from context",
    "attention layers mix information across positions",
    "training minimizes the masked language loss",
    "tokenizers split words into subword pieces",
] * 8


def main(epochs: int = 2, seq_len: int = 16, batch_size: int = 8,
         corpus=None, verbose: bool = True):
    corpus = corpus or CORPUS
    vocab = build_vocab(corpus, max_size=512)
    tok = BertWordPieceTokenizer(vocab)
    it = BertIterator(tok, CollectionSentenceProvider(corpus),
                      seq_len=seq_len, batch_size=batch_size, seed=7)

    config = BertConfig(vocab_size=len(vocab), hidden_size=64, num_layers=2,
                        num_heads=2, intermediate_size=128,
                        max_position=seq_len)
    model = BertForMaskedLM(config, seed=0)
    from deeplearning4j_tpu.obs import CollectScoresListener
    # converts EVERY score, so the loop waits for every step (fine at this
    # size); ScoreIterationListener(n) reads one in n and lets steps pipeline
    scores = CollectScoresListener()
    model.fit(it, updater=Adam(5e-4), epochs=epochs, listeners=[scores])
    losses = scores.scores
    if verbose:
        print(f"first loss {losses[0]:.3f} -> last {losses[-1]:.3f}")
    return losses


if __name__ == "__main__":
    main()
