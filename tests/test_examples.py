"""Smoke-runs every example with tiny arguments (reference analog:
example mains exercised in CI, SURVEY.md §2.12 L12)."""

import importlib.util
import os

import numpy as np
import pytest

EXAMPLES = os.path.join(os.path.dirname(__file__), "..",
                        "analytics_zoo_tpu", "examples")


def _run(name, argv):
    path = os.path.join(EXAMPLES, name + ".py")
    spec = importlib.util.spec_from_file_location(f"example_{name}",
                                                 path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.main(argv)


def test_lenet_mnist():
    metrics = _run("lenet_mnist", ["--n-train", "64", "--n-test", "32",
                                   "--batch-size", "32", "--epochs",
                                   "1"])
    assert "loss" in metrics


def test_ncf_recommendation():
    recs = _run("ncf_recommendation",
                ["--samples", "256", "--users", "20", "--items", "30",
                 "--batch-size", "64", "--epochs", "1"])
    assert len(recs) > 0


def test_text_classification():
    metrics = _run("text_classification",
                   ["--per-class", "16", "--epochs", "1",
                    "--sequence-length", "16"])
    assert "loss" in metrics


def test_anomaly_detection():
    flagged = _run("anomaly_detection",
                   ["--points", "200", "--unroll", "12", "--epochs",
                    "1", "--batch-size", "32"])
    assert len(flagged) >= 1


def test_object_detection():
    results = _run("object_detection", ["--images", "1"])
    assert len(results) == 1


def test_tfpark_keras():
    pytest.importorskip("tensorflow")
    after = _run("tfpark_keras", ["--samples", "128", "--epochs", "2",
                                  "--batch-size", "32"])
    assert after < 100


def test_nnframes_classification():
    acc = _run("nnframes_classification",
               ["--samples", "64", "--epochs", "2"])
    assert 0.0 <= acc <= 1.0


def test_onnx_import(tmp_path):
    _run("onnx_import", ["--path", str(tmp_path / "m.onnx"),
                         "--epochs", "1"])


def test_distributed_training():
    _run("distributed_training", ["--devices", "4",
                                  "--batch-per-device", "2",
                                  "--steps", "2"])


def test_inference_serving():
    results = _run("inference_serving", ["--concurrency", "2",
                                         "--requests", "4"])
    assert all(r is not None for r in results)

def test_rdd_ingest():
    metrics = _run("rdd_ingest", ["--n", "64", "--epochs", "1",
                                  "--batch-size", "16"])
    assert "loss" in metrics


def test_quantized_serving():
    result = _run("quantized_serving", ["--n", "128", "--epochs", "2"])
    assert result["agreement"] >= 0.95
    assert result["kernel_bytes_f32"] > 2 * result["kernel_bytes_int8"]


def test_long_context():
    # small T so the Pallas-interpret flash path stays fast on CPU
    _run("long_context", ["--seq-len", "1024"])


def test_autograd_custom():
    result = _run("autograd_custom", ["--n", "256", "--epochs", "40"])
    # mae shrinks and weights head toward [2, 2]
    assert result["mae"] < 0.2, result


def test_qa_ranker():
    metrics = _run("qa_ranker", ["--nb-epoch", "2",
                                 "--answer-length", "12"])
    for k in ("ndcg@3", "ndcg@5", "map"):
        assert 0.0 <= metrics[k] <= 1.0


def test_transformer_sentiment():
    metrics = _run("transformer_sentiment",
                   ["--max-len", "16", "--n-train", "64",
                    "--hidden-size", "16", "--n-head", "2",
                    "--max-features", "500"])
    assert "loss" in metrics


def test_image_classification_predict():
    results = _run("image_classification",
                   ["--image-size", "32", "--classes", "5",
                    "--model", "squeezenet", "--top-n", "2"])
    assert len(results) == 4
    for uri, top in results:
        assert len(top) == 2
        assert all(0 <= c < 5 for c, _ in top)


def test_vae_mnist():
    result = _run("vae_mnist", ["--n-train", "128", "--epochs", "1",
                                "--hidden", "32"])
    assert np.isfinite(result["loss"])
    assert result["samples"].shape == (4, 784)
    assert 0.0 <= result["samples"].min() and \
        result["samples"].max() <= 1.0


def test_transfer_learning():
    metrics = _run("transfer_learning", ["--n", "64", "--epochs", "1",
                                         "--image-size", "16"])
    assert "loss" in metrics


def test_wide_and_deep():
    metrics = _run("wide_and_deep",
                   ["--samples", "1024", "--epochs", "2",
                    "--batch-size", "256", "--users", "50",
                    "--items", "40"])
    assert metrics["accuracy"] > 0.25   # 5 classes: chance is 0.2


def test_bert_finetune():
    scores = _run("bert_finetune",
                  ["--devices", "2", "--seq-len", "32", "--hidden",
                   "32", "--blocks", "1", "--batch-per-device", "2",
                   "--epochs", "1"])
    assert "accuracy" in scores


def test_bert_finetune_frozen_encoder():
    scores = _run("bert_finetune",
                  ["--devices", "2", "--seq-len", "32", "--hidden",
                   "32", "--blocks", "1", "--batch-per-device", "2",
                   "--epochs", "1", "--freeze-encoder"])
    assert np.isfinite(scores["loss"])


def test_resnet_imagenet_recipe(tmp_path):
    from PIL import Image
    rs = np.random.RandomState(0)
    for cls in ("cat", "dog"):
        (tmp_path / cls).mkdir()
        for i in range(4):
            Image.fromarray(
                rs.randint(0, 255, (40, 40, 3)).astype(np.uint8)) \
                .save(tmp_path / cls / f"{i}.png")
    hist = _run("resnet_imagenet",
                ["--folder", str(tmp_path), "--devices", "2",
                 "--image-size", "32", "--batch-per-device", "2",
                 "--epochs", "1",
                 "--checkpoint", str(tmp_path / "ck")])
    assert np.isfinite(hist[-1]["loss"])
    assert (tmp_path / "ck" / "LATEST").exists()


def test_chatbot():
    r = _run("chatbot", ["--epochs", "3", "--hidden", "16"])
    assert np.isfinite(r["loss"])
    assert isinstance(r["reply"], str)


def test_streaming_inference():
    r = _run("streaming_inference",
             ["--records", "24", "--rate", "3000",
              "--batch-max", "8", "--batch-interval-ms", "50"])
    assert r["records"] == 24
    assert r["batches"] >= 3


def test_examples_cli_list_and_dispatch(capsys):
    from analytics_zoo_tpu.examples.__main__ import main
    assert main(["list"]) == 0
    out = capsys.readouterr().out
    assert "lenet_mnist" in out
    assert "LeNet training example" in out   # docstring hooks render
    assert main(["nope"]) == 2
