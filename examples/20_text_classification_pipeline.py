"""Example 20 — the text-classification pipeline, end to end.

Covers the reference's NLP data tier the way a DL4J user would use it:
word2vec embeddings -> CnnSentenceDataSetIterator (Kim-2014 CNN batches)
-> Conv2D + GlobalPooling classifier, plus the supporting text tooling
(sentence/document iterators, stemming preprocessors, POS filtering,
SentiWordNet polarity, constituency-tree utilities).

Reference counterparts: iterator/CnnSentenceDataSetIterator.java,
text/sentenceiterator + documentiterator packages, nlp-uima's
StemmingPreprocessor/PosUimaTokenizer/SWN3/treeparser.

Run: JAX_PLATFORMS=cpu python examples/20_text_classification_pipeline.py   (small demo)
"""

import random

import numpy as np

from deeplearning4j_tpu.nlp import (
    PorterStemmer,
    PosTokenizerFactory,
    StemmingPreprocessor,
    SWN3,
    Tree,
    TreeVectorizer,
    Word2Vec,
)
from deeplearning4j_tpu.nlp.cnn_sentence import (
    CnnSentenceDataSetIterator,
    CollectionLabeledSentenceProvider,
)
from deeplearning4j_tpu.nn.conf import InputType, NeuralNetConfiguration
from deeplearning4j_tpu.nn.layers import ConvolutionLayer, OutputLayer
from deeplearning4j_tpu.nn.layers.pooling import GlobalPoolingLayer
from deeplearning4j_tpu.nn.multilayer import MultiLayerNetwork

# --- 1. train word vectors on a toy corpus --------------------------------
animals = ["cat dog purr bark fur", "dog cat tail paw fur",
           "cat purr fur paw bark"]
tech = ["cpu gpu cache chip core", "gpu cpu silicon chip core",
        "cpu cache chip core silicon"]
corpus = [s.split() for s in (animals + tech) * 30]
w2v = Word2Vec(layer_size=16, window_size=3, min_word_frequency=1,
               seed=7, epochs=10)
w2v.fit(corpus)
print(f"word2vec: {w2v.vocab.num_words()} words, "
      f"nearest to 'cat': {w2v.words_nearest('cat', 3)}")

# --- 2. CNN sentence batches ----------------------------------------------
sents, labels = [], []
for s in animals * 8:
    sents.append(s), labels.append("animal")
for s in tech * 8:
    sents.append(s), labels.append("tech")
provider = CollectionLabeledSentenceProvider(sents, labels,
                                             rng=random.Random(3))
it = CnnSentenceDataSetIterator(provider, w2v, minibatch_size=8,
                                max_sentence_length=5,
                                feature_format="NHWC")
print(f"labels: {it.get_labels()}, word-vector size {it.input_columns()}")

# --- 3. Kim-style conv classifier -----------------------------------------
conf = (NeuralNetConfiguration.builder().seed(5).updater("adam").list()
        .layer(ConvolutionLayer(n_out=8, kernel_size=(2, 16),
                                convolution_mode="same", activation="relu"))
        .layer(GlobalPoolingLayer(pooling_type="max"))
        .layer(OutputLayer(n_out=2))
        .set_input_type(InputType.convolutional(5, 16, 1))
        .build())
net = MultiLayerNetwork(conf).init()
for _ in range(30):
    for ds in it:
        net.fit(ds.features, ds.labels)

correct = total = 0
it.reset()
for ds in it:
    out = np.asarray(net.output(ds.features))
    correct += int((out.argmax(1) == ds.labels.argmax(1)).sum())
    total += len(out)
print(f"sentence-CNN train accuracy: {correct / total:.2f}")
pred = np.asarray(net.output(it.load_single_sentence("purr paw fur")))
print(f"'purr paw fur' -> {it.get_labels()[int(pred.argmax())]}")

# --- 4. the supporting text tooling ---------------------------------------
stem = PorterStemmer()
print("stems:", [stem.stem(w) for w in ["motoring", "relational", "ponies"]])
pre = StemmingPreprocessor()
print("stemming preprocessor:", pre.pre_process("Conflated,"))

pos = PosTokenizerFactory(allowed_pos_tags={"NN", "NNS"}, strip_nones=True)
print("nouns only:", pos.create("the cat is running quickly").get_tokens())

swn = SWN3()
for text in ("a good movie", "not a good movie", "terrible awful plot"):
    print(f"sentiment {text!r}: {swn.classify(text)}")

tree = Tree.from_penn(
    "(S (NP (DT the) (NN cat)) (VP (VBZ sits) (PP (IN on) (NP (DT the) (NN mat)))))")
tv = TreeVectorizer()
[normalized] = tv.get_trees_with_labels([tree.to_penn()], "pos", ["neg", "pos"])
print("tree yield:", normalized.yield_words(),
      "gold label on root:", normalized.gold_label)
