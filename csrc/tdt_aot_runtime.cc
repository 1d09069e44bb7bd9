// tdt_aot_runtime — standalone C++ serving runtime over the PJRT C API.
//
// Reference: python/triton_dist/tools/runtime/triton_aot_runtime.{cc,h} —
// a CUDA-driver runtime that loads AOT-compiled kernels and launches them
// without Python. TPU equivalent: load the PJRT plugin named on the command
// line (libtpu or any conforming backend),
// compile the StableHLO module exported by triton_dist_tpu.tools.aot, feed
// it raw input buffers, and write raw outputs — a full serving round-trip
// with zero Python in the process.
//
// Usage:
//   tdt_aot_run <plugin.so> <artifact_dir> [iters]
// where <artifact_dir> contains (written by tools/aot.py::export_aot):
//   program.mlir        — StableHLO module text
//   compile_options.pb  — serialized xla.CompileOptionsProto
//   manifest.txt        — one line per input:  dtype ndim d0 d1 ...
//   input_<i>.bin       — raw little-endian input bytes
// outputs are written to  output_<i>.bin  and wall/exec times printed.
//
// Build (tools/aot.py::build_runtime shells out to exactly this):
//   g++ -O2 -std=c++17 -I<tf_include> csrc/tdt_aot_runtime.cc -ldl \
//       -o tdt_aot_run

#include <dlfcn.h>

#include <chrono>
#include <cstdint>
#include <cstdio>
#include <cstring>
#include <fstream>
#include <sstream>
#include <string>
#include <vector>

#include "tensorflow/compiler/xla/pjrt/c/pjrt_c_api.h"

namespace {

const PJRT_Api* g_api = nullptr;

void Check(PJRT_Error* err, const char* what) {
  if (err == nullptr) return;
  PJRT_Error_Message_Args margs;
  memset(&margs, 0, sizeof(margs));
  margs.struct_size = PJRT_Error_Message_Args_STRUCT_SIZE;
  margs.error = err;
  g_api->PJRT_Error_Message(&margs);
  fprintf(stderr, "FATAL %s: %.*s\n", what, (int)margs.message_size,
          margs.message);
  PJRT_Error_Destroy_Args dargs;
  memset(&dargs, 0, sizeof(dargs));
  dargs.struct_size = PJRT_Error_Destroy_Args_STRUCT_SIZE;
  dargs.error = err;
  g_api->PJRT_Error_Destroy(&dargs);
  exit(1);
}

void AwaitEvent(PJRT_Event* ev, const char* what) {
  if (ev == nullptr) return;
  PJRT_Event_Await_Args aw;
  memset(&aw, 0, sizeof(aw));
  aw.struct_size = PJRT_Event_Await_Args_STRUCT_SIZE;
  aw.event = ev;
  Check(g_api->PJRT_Event_Await(&aw), what);
  PJRT_Event_Destroy_Args de;
  memset(&de, 0, sizeof(de));
  de.struct_size = PJRT_Event_Destroy_Args_STRUCT_SIZE;
  de.event = ev;
  Check(g_api->PJRT_Event_Destroy(&de), "event destroy");
}

std::string ReadFile(const std::string& path) {
  std::ifstream f(path, std::ios::binary);
  if (!f) {
    fprintf(stderr, "FATAL cannot read %s\n", path.c_str());
    exit(1);
  }
  std::ostringstream ss;
  ss << f.rdbuf();
  return ss.str();
}

struct InputSpec {
  PJRT_Buffer_Type type;
  std::vector<int64_t> dims;
};

// Client-create options from <dir>/options.txt: one "s <key> <value>" or
// "i <key> <value>" per line (plugin-specific NamedValues; empty/missing
// file = no options).
struct Options {
  std::vector<std::string> keys;
  std::vector<std::string> svals;
  std::vector<int64_t> ivals;
  std::vector<char> is_int;
  std::vector<PJRT_NamedValue> values;

  void Load(const std::string& path) {
    std::ifstream f(path);
    if (!f) return;
    std::string type, key;
    while (f >> type >> key) {
      keys.push_back(key);
      if (type == "i") {
        int64_t v;
        f >> v;
        ivals.push_back(v);
        svals.emplace_back();
        is_int.push_back(1);
      } else {
        std::string v;
        f >> v;
        svals.push_back(v);
        ivals.push_back(0);
        is_int.push_back(0);
      }
    }
    values.resize(keys.size());
    for (size_t i = 0; i < keys.size(); ++i) {
      PJRT_NamedValue& nv = values[i];
      memset(&nv, 0, sizeof(nv));
      nv.struct_size = PJRT_NamedValue_STRUCT_SIZE;
      nv.name = keys[i].c_str();
      nv.name_size = keys[i].size();
      if (is_int[i]) {
        nv.type = PJRT_NamedValue_kInt64;
        nv.int64_value = ivals[i];
        nv.value_size = 1;
      } else {
        nv.type = PJRT_NamedValue_kString;
        nv.string_value = svals[i].c_str();
        nv.value_size = svals[i].size();
      }
    }
  }
};

PJRT_Buffer_Type ParseDtype(const std::string& s) {
  if (s == "f32") return PJRT_Buffer_Type_F32;
  if (s == "bf16") return PJRT_Buffer_Type_BF16;
  if (s == "f16") return PJRT_Buffer_Type_F16;
  if (s == "i32") return PJRT_Buffer_Type_S32;
  if (s == "i8") return PJRT_Buffer_Type_S8;
  if (s == "u8") return PJRT_Buffer_Type_U8;
  fprintf(stderr, "FATAL unsupported dtype %s\n", s.c_str());
  exit(1);
}

}  // namespace

int main(int argc, char** argv) {
  if (argc < 3) {
    fprintf(stderr, "usage: %s <plugin.so> <artifact_dir> [iters]\n", argv[0]);
    return 2;
  }
  const std::string plugin = argv[1];
  const std::string dir = argv[2];
  const int iters = argc > 3 ? atoi(argv[3]) : 1;

  void* handle = dlopen(plugin.c_str(), RTLD_NOW | RTLD_GLOBAL);
  if (!handle) {
    fprintf(stderr, "FATAL dlopen %s: %s\n", plugin.c_str(), dlerror());
    return 1;
  }
  auto get_api = reinterpret_cast<const PJRT_Api* (*)()>(
      dlsym(handle, "GetPjrtApi"));
  if (!get_api) {
    fprintf(stderr, "FATAL no GetPjrtApi in %s\n", plugin.c_str());
    return 1;
  }
  g_api = get_api();
  printf("pjrt api %d.%d\n", g_api->pjrt_api_version.major_version,
         g_api->pjrt_api_version.minor_version);

  {
    PJRT_Plugin_Initialize_Args init;
    memset(&init, 0, sizeof(init));
    init.struct_size = PJRT_Plugin_Initialize_Args_STRUCT_SIZE;
    Check(g_api->PJRT_Plugin_Initialize(&init), "plugin init");
  }

  Options opts_file;
  opts_file.Load(dir + "/options.txt");

  PJRT_Client* client = nullptr;
  {
    PJRT_Client_Create_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Create_Args_STRUCT_SIZE;
    args.create_options = opts_file.values.data();
    args.num_options = opts_file.values.size();
    Check(g_api->PJRT_Client_Create(&args), "client create");
    client = args.client;
  }

  PJRT_Device* device = nullptr;
  {
    PJRT_Client_AddressableDevices_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_AddressableDevices_Args_STRUCT_SIZE;
    args.client = client;
    Check(g_api->PJRT_Client_AddressableDevices(&args), "devices");
    if (args.num_addressable_devices == 0) {
      fprintf(stderr, "FATAL no addressable devices\n");
      return 1;
    }
    device = args.addressable_devices[0];
    printf("devices: %zu\n", args.num_addressable_devices);
  }

  // ---- compile the exported StableHLO module
  std::string mlir = ReadFile(dir + "/program.mlir");
  std::string copts = ReadFile(dir + "/compile_options.pb");
  PJRT_LoadedExecutable* exec = nullptr;
  {
    PJRT_Program program;
    memset(&program, 0, sizeof(program));
    program.struct_size = PJRT_Program_STRUCT_SIZE;
    program.code = mlir.data();
    program.code_size = mlir.size();
    static const char kFormat[] = "mlir";
    program.format = kFormat;
    program.format_size = sizeof(kFormat) - 1;

    PJRT_Client_Compile_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_Compile_Args_STRUCT_SIZE;
    args.client = client;
    args.program = &program;
    args.compile_options = copts.data();
    args.compile_options_size = copts.size();
    auto t0 = std::chrono::steady_clock::now();
    Check(g_api->PJRT_Client_Compile(&args), "compile");
    exec = args.executable;
    double ms = std::chrono::duration<double, std::milli>(
                    std::chrono::steady_clock::now() - t0)
                    .count();
    printf("compile_ms: %.1f\n", ms);
  }

  // ---- stage inputs
  std::vector<InputSpec> specs;
  {
    std::istringstream mf(ReadFile(dir + "/manifest.txt"));
    std::string line;
    while (std::getline(mf, line)) {
      if (line.empty()) continue;
      std::istringstream ls(line);
      std::string dtype;
      size_t ndim;
      ls >> dtype >> ndim;
      InputSpec spec;
      spec.type = ParseDtype(dtype);
      for (size_t i = 0; i < ndim; ++i) {
        int64_t d;
        ls >> d;
        spec.dims.push_back(d);
      }
      specs.push_back(std::move(spec));
    }
  }
  std::vector<std::string> host_data(specs.size());
  std::vector<PJRT_Buffer*> inputs(specs.size());
  for (size_t i = 0; i < specs.size(); ++i) {
    host_data[i] = ReadFile(dir + "/input_" + std::to_string(i) + ".bin");
    PJRT_Client_BufferFromHostBuffer_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Client_BufferFromHostBuffer_Args_STRUCT_SIZE;
    args.client = client;
    args.data = host_data[i].data();
    args.type = specs[i].type;
    args.dims = specs[i].dims.data();
    args.num_dims = specs[i].dims.size();
    args.host_buffer_semantics =
        PJRT_HostBufferSemantics_kImmutableUntilTransferCompletes;
    args.device = device;
    Check(g_api->PJRT_Client_BufferFromHostBuffer(&args), "h2d");
    AwaitEvent(args.done_with_host_buffer, "h2d done");
    inputs[i] = args.buffer;
  }

  // ---- output arity
  size_t num_outputs = 0;
  {
    PJRT_LoadedExecutable_GetExecutable_Args ge;
    memset(&ge, 0, sizeof(ge));
    ge.struct_size = PJRT_LoadedExecutable_GetExecutable_Args_STRUCT_SIZE;
    ge.loaded_executable = exec;
    Check(g_api->PJRT_LoadedExecutable_GetExecutable(&ge), "get exec");
    PJRT_Executable_NumOutputs_Args no;
    memset(&no, 0, sizeof(no));
    no.struct_size = PJRT_Executable_NumOutputs_Args_STRUCT_SIZE;
    no.executable = ge.executable;
    Check(g_api->PJRT_Executable_NumOutputs(&no), "num outputs");
    num_outputs = no.num_outputs;
  }
  printf("num_inputs: %zu num_outputs: %zu\n", specs.size(), num_outputs);

  // ---- execute (iters times; buffers re-used, last outputs kept)
  std::vector<PJRT_Buffer*> outputs(num_outputs, nullptr);
  double total_ms = 0;
  for (int it = 0; it < iters; ++it) {
    for (auto* b : outputs) {
      if (b) {
        PJRT_Buffer_Destroy_Args dbe;
        memset(&dbe, 0, sizeof(dbe));
        dbe.struct_size = PJRT_Buffer_Destroy_Args_STRUCT_SIZE;
        dbe.buffer = b;
        Check(g_api->PJRT_Buffer_Destroy(&dbe), "old out destroy");
      }
    }
    PJRT_ExecuteOptions opts;
    memset(&opts, 0, sizeof(opts));
    opts.struct_size = PJRT_ExecuteOptions_STRUCT_SIZE;
    PJRT_Buffer* const* arg_list = inputs.data();
    PJRT_Buffer** out_list = outputs.data();
    PJRT_Event* done = nullptr;

    PJRT_LoadedExecutable_Execute_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_LoadedExecutable_Execute_Args_STRUCT_SIZE;
    args.executable = exec;
    args.options = &opts;
    args.argument_lists = &arg_list;
    args.num_devices = 1;
    args.num_args = inputs.size();
    args.output_lists = &out_list;
    args.device_complete_events = &done;
    auto t0 = std::chrono::steady_clock::now();
    Check(g_api->PJRT_LoadedExecutable_Execute(&args), "execute");
    AwaitEvent(done, "execute done");
    total_ms += std::chrono::duration<double, std::milli>(
                    std::chrono::steady_clock::now() - t0)
                    .count();
  }
  printf("exec_ms_avg: %.3f\n", total_ms / iters);

  // ---- read back + write output_<i>.bin
  for (size_t i = 0; i < num_outputs; ++i) {
    PJRT_Buffer_ToHostBuffer_Args args;
    memset(&args, 0, sizeof(args));
    args.struct_size = PJRT_Buffer_ToHostBuffer_Args_STRUCT_SIZE;
    args.src = outputs[i];
    Check(g_api->PJRT_Buffer_ToHostBuffer(&args), "d2h size query");
    std::string out(args.dst_size, '\0');
    args.dst = out.data();
    Check(g_api->PJRT_Buffer_ToHostBuffer(&args), "d2h");
    AwaitEvent(args.event, "d2h done");
    std::ofstream f(dir + "/output_" + std::to_string(i) + ".bin",
                    std::ios::binary);
    f.write(out.data(), out.size());
    printf("output_%zu: %zu bytes\n", i, out.size());
  }

  printf("OK\n");
  return 0;
}
