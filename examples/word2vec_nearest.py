"""Word2Vec on raw text + nearest-word queries (ref: dl4j-examples
Word2VecRawTextExample). Hogwild threads become batched negative-sampling
updates under jit (SURVEY §2.9 P12).
"""
import _bootstrap  # noqa: F401  (repo path + XLA_FLAGS)

from deeplearning4j_tpu.text import (
    CollectionSentenceIterator, DefaultTokenizerFactory, Word2Vec)

# tiny synthetic corpus with clear co-occurrence structure
animals = ["cat", "dog", "horse", "cow"]
foods = ["bread", "cheese", "apple", "rice"]
sentences = []
for i in range(300):
    a, b = animals[i % 4], animals[(i + 1) % 4]
    f, g = foods[i % 4], foods[(i + 3) % 4]
    sentences += [f"the {a} chased the {b} across the field",
                  f"we ate {f} and {g} for dinner"]

vec = Word2Vec(minWordFrequency=2, layerSize=32, seed=42, windowSize=4,
               epochs=8, negativeSample=5,
               iterate=CollectionSentenceIterator(sentences),
               tokenizerFactory=DefaultTokenizerFactory())
vec.fit()

print("closest to 'cat':", vec.wordsNearest("cat", 3))
print("closest to 'cheese':", vec.wordsNearest("cheese", 3))
sim_aa = vec.similarity("cat", "dog")
sim_af = vec.similarity("cat", "bread")
print(f"sim(cat,dog)={sim_aa:.3f}  sim(cat,bread)={sim_af:.3f}")
assert sim_aa > sim_af

# t-SNE page of the learned vectors (ref: UI tsne tab / TSNEStandardExample)
from deeplearning4j_tpu.ui import render_word_vectors

path = render_word_vectors(vec, "/tmp/word_vectors_tsne.html", perplexity=5)
print("t-SNE page:", path)
